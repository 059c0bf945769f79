"""End-to-end LM training driver.

Runs any `--arch` (reduced smoke config by default, full config with
--full) on the local mesh with the same step builders the dry-run lowers
for the production meshes.  Fault-tolerant by construction:

  * checkpoints (params + optimizer + data cursor) every N steps, atomic,
    keep-K, auto-resume on restart — kill the process mid-run and rerun
    the same command to continue;
  * elastic: a resume may use a different device count / mesh shape — the
    checkpointer stores unsharded arrays and re-shards on load
    (launch/mesh.make_elastic_mesh);
  * straggler mitigation on real multi-host pods is the runtime's
    responsibility (TPU SPMD is bulk-synchronous): we surface it by (a)
    per-step wall-clock logging for detection and (b) deterministic
    checkpoint-resume for the mitigation path (restart the sick host).

On real TPU pods, set these XLA flags for collective/compute overlap
(latency-hiding scheduler):
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_overlap_compute_collective_tc=true
  --xla_enable_async_all_gather=true

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
      --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt_qwen3
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import TrainConfig
from repro.data import synthetic
from repro.data.pipeline import BatchIterator, lm_batches
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.sharding import DistContext, param_pspecs
from repro.checkpoint.checkpointer import Checkpointer
from repro.optim import optimizers as opt_lib
from repro.serving.failpoints import FailPlan


def run(arch: str, steps: int = 100, batch: int = 8, seq: int = 64,
        ckpt_dir: str | None = None, full: bool = False,
        bloom: bool = True, log_every: int = 10, microbatch: int = 0,
        grad_compression: str = "none", seed: int = 0,
        fault_at: int = -1, learning_rate: float = 3e-3,
        io_impl: str | None = None, bwd_impl: str | None = None,
        table_dtype: str | None = None,
        failpoints: str | None = None):
    cfg = (configs.get_config(arch, bloom=bloom) if full
           else configs.get_smoke_config(arch))
    import dataclasses
    if io_impl is not None:
        cfg = dataclasses.replace(cfg, io_impl=io_impl)
    if bwd_impl is not None:
        cfg = dataclasses.replace(cfg, bwd_impl=bwd_impl)
    if table_dtype is not None:
        cfg = dataclasses.replace(cfg, table_dtype=table_dtype)
    mesh = make_local_mesh()
    dist = DistContext(mesh) if mesh.size > 1 else None
    tc = TrainConfig(optimizer="adamw", learning_rate=learning_rate,
                     grad_clip_norm=1.0, steps=steps, warmup_steps=10,
                     checkpoint_every=max(steps // 4, 10),
                     microbatch=microbatch,
                     grad_compression=grad_compression)

    # data: synthetic Zipf token stream shaped like the cell's inputs
    stream = synthetic.make_token_stream(
        n_tokens=batch * (seq + 1) * max(steps, 64), vocab=cfg.vocab,
        seed=seed)
    windows = lm_batches(stream, batch, seq)
    it = BatchIterator([windows], batch, seed=seed)

    def make_batch(arrays):
        w = jnp.asarray(arrays[0])
        b = {"tokens": w[:, :]}
        if cfg.family in ("vlm", "audio"):
            n_emb = max(4, seq // 4)
            b["embeds"] = jnp.zeros((w.shape[0], n_emb, cfg.d_model),
                                    jnp.dtype(cfg.dtype))
        return b

    step_fn, optimizer = steps_lib.make_train_step(cfg, tc, dist)
    step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    init = steps_lib.init_fn_for(cfg)
    params = init(jax.random.PRNGKey(seed))
    opt_state = optimizer.init(params)
    start_step = 0

    ckpt = Checkpointer(ckpt_dir, keep=tc.keep_checkpoints,
                        async_write=True) if ckpt_dir else None
    if ckpt:
        restored, rstep, extra = ckpt.restore_latest(
            {"params": params, "opt_state": opt_state})
        if restored is not None:
            params = restored["params"]
            opt_state = restored["opt_state"]
            start_step = rstep
            if "data" in extra:
                it.restore(extra["data"])
            print(f"resumed from step {rstep}")

    # Fault injection goes through the same seeded registry the serving
    # stack uses (serving/failpoints.py); --fault-at is sugar for
    # `train_fault@S`, and both compose in one plan.
    plan = FailPlan.parse(failpoints)
    if fault_at >= 0:
        plan = plan.merge(FailPlan.parse(f"train_fault@{fault_at}"))
    fault_hook = plan.train_hook()

    history = []
    t_start = time.perf_counter()
    for s in range(start_step, steps):
        if fault_hook is not None:
            fault_hook(s)
        arrays = next(it)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_jit(params, opt_state,
                                              make_batch(arrays))
        if log_every and (s + 1) % log_every == 0:
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            history.append({"step": s + 1, "loss": loss, "step_s": dt})
            print(f"step {s+1:5d}  loss {loss:.4f}  {dt*1e3:.0f} ms",
                  flush=True)
        if ckpt and (s + 1) % tc.checkpoint_every == 0:
            ckpt.save(s + 1, {"params": params, "opt_state": opt_state},
                      extra={"data": it.state()}, block=False)
    if ckpt:
        ckpt.save(steps, {"params": params, "opt_state": opt_state},
                  extra={"data": it.state()})
        ckpt.wait()
    wall = time.perf_counter() - t_start
    print(f"trained {steps - start_step} steps in {wall:.1f}s")
    return params, history


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    choices=list(configs.ARCH_NAMES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-bloom", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "bf16"])
    ap.add_argument("--fault-at", type=int, default=-1,
                    help="raise at this step (fault-tolerance demo); "
                         "sugar for --failpoints train_fault@S")
    ap.add_argument("--failpoints", default=None,
                    help="failpoint spec (serving/failpoints.py grammar), "
                         "e.g. train_fault@7")
    ap.add_argument("--io-impl", default=None, choices=["xla", "pallas"],
                    help="override cfg.io_impl (pallas = fused Bloom "
                         "embed/CE kernels in the train step)")
    ap.add_argument("--bwd-impl", default=None, choices=["dense", "csr"],
                    help="pallas-path Bloom backward: csr (CSR-binned "
                         "scatter-add, stream-once) or dense (m-tile "
                         "sweep fallback)")
    ap.add_argument("--table-dtype", default=None,
                    choices=["auto", "float32", "bfloat16", "int8",
                             "fp8_e4m3"],
                    help="Bloom table storage dtype (DESIGN.md §13); "
                         "auto = legacy cast-to-activation-dtype; int8 "
                         "uses per-row scales with straight-through "
                         "gradients (quantization-aware training)")
    args = ap.parse_args()
    run(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
        ckpt_dir=args.ckpt, full=args.full, bloom=not args.no_bloom,
        microbatch=args.microbatch, grad_compression=args.grad_compression,
        fault_at=args.fault_at, io_impl=args.io_impl,
        bwd_impl=args.bwd_impl, table_dtype=args.table_dtype,
        failpoints=args.failpoints)


if __name__ == "__main__":
    main()
