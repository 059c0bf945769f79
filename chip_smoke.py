"""Chip smoke test: the system's main paths, once, on a TPU.

Runs in ONE process and stops with a nonzero exit at the first failure:

  (a) device check — the first device must be a TPU;
  (b) LM serving — qwen1.5-0.5b at published widths through
      ``launch/serve.run_continuous`` with the Pallas Bloom IO kernels,
      then the same seeded workload with the XLA reference IO;
  (c) training — ``launch/train.run``, 4 steps at batch 8 x seq 512 with
      the Pallas embed / CE kernels and the CSR backward, against the XLA
      run's step-1 loss;
  (d) retrieval — ``launch/serve.run_retrieval`` over the 10M-item
      ``web10m`` catalog, checked against ``core.bloom.decode_topk``.

Weights are random, made from a fixed seed.  Each phase prints its
compile seconds, run seconds and the device's ``peak_bytes_in_use``; the
last line of stdout is one JSON object naming the device.

    python chip_smoke.py                # one chip: phases (b)-(d)
    python chip_smoke.py --four-chips   # four chips: the sharded pool only

``--four-chips`` runs ``ShardedEngine`` over the 4-device serving mesh
(4 hosts x 2 slots, collective transport) against a single-host 2-slot
``Engine`` on the merged workload.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen1.5-0.5b"
RETRIEVAL = "web10m"

# compile time = lowering to MLIR + backend compile (persistent-cache
# reads included), summed from JAX's own duration events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Phase:
    """Times one phase: compile seconds from the events above, run
    seconds as the rest of the wall clock."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.c0 = _compile_s[0]
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        comp = _compile_s[0] - self.c0
        stats = jax.devices()[0].memory_stats() or {}
        print(f"phase {self.name}: compile_s {comp:.3f} run_s "
              f"{wall - comp:.3f} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'n/a')}", flush=True)
        return False


def check(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_check(n_chips):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    check(len(devs) >= n_chips,
          f"need {n_chips} TPU devices, found {len(devs)}")
    print(f"device_kind {devs[0].device_kind} x{len(devs)}", flush=True)
    return devs[0]


def assert_kernels_compiled(name, fn, *abstract_args):
    """The jitted step must lower with the Pallas kernels as Mosaic custom
    calls (never interpret mode, never the XLA reference)."""
    text = jax.jit(fn).lower(*abstract_args).as_text()
    check("tpu_custom_call" in text,
          f"{name}: lowered step holds no tpu_custom_call")
    print(f"{name}: tpu_custom_call present", flush=True)


def _lm_config(arch, full, io_impl):
    from repro import configs
    cfg = (configs.get_config(arch) if full
           else configs.get_smoke_config(arch))
    return dataclasses.replace(cfg, io_impl=io_impl)


def _lm_params_abstract(cfg):
    from repro.launch import steps as steps_lib
    return jax.eval_shape(lambda k: steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(k), cfg), jax.random.PRNGKey(0))


# --------------------------------------------------------------------------
# (b) LM serving
# --------------------------------------------------------------------------

def serve_phase(arch=ARCH, full=True, slots=8, requests=16, prompt_len=128,
                gen=32, topk=8):
    from repro.launch import serve
    from repro.launch import steps as steps_lib
    from repro.models import transformer as tf
    S = jax.ShapeDtypeStruct
    kw = dict(slots=slots, requests=requests, prompt_len=prompt_len,
              gen=gen, topk=topk, full=full)

    cfg = _lm_config(arch, full, "pallas")
    max_len = prompt_len + gen
    caches = jax.eval_shape(lambda: tf.init_lm_cache(
        cfg, slots, max_len, dtype=jnp.dtype(cfg.dtype)))
    assert_kernels_compiled(
        "serve decode step",
        steps_lib.make_slot_decode_step(cfg, topk=topk),
        _lm_params_abstract(cfg), S((slots, 1), jnp.int32), caches,
        S((slots,), jnp.int32), S((slots,), jnp.bool_))

    runs = {}
    for impl in ("pallas", "xla"):
        with Phase(f"serve.{impl}"):
            res, stats = serve.run_continuous(arch, io_impl=impl, **kw)
        check(len(res) == requests, f"serve.{impl}: {len(res)} results "
              f"for {requests} requests")
        check(stats.rejects == 0, f"serve.{impl}: {stats.rejects} rejects")
        check(all(r.done and not r.rejected and not r.shed and r.tokens
                  for r in res.values()),
              f"serve.{impl}: not every request was served")
        runs[impl] = {rid: r.tokens for rid, r in res.items()}

    pal, xla = runs["pallas"], runs["xla"]
    firsts = [rid for rid in pal if pal[rid][0] != xla[rid][0]]
    check(not firsts, f"first tokens differ between pallas and xla for "
          f"requests {firsts}")
    total = sum(len(t) for t in pal.values())
    same = sum(a == b for rid in pal for a, b in zip(pal[rid], xla[rid]))
    print(f"serve: {requests} requests served, first tokens identical; "
          f"{same}/{total} tokens match pallas vs xla "
          f"({same / total:.4f})", flush=True)


# --------------------------------------------------------------------------
# (c) training
# --------------------------------------------------------------------------

def train_phase(arch=ARCH, full=True, steps=4, batch=8, seq=512):
    from repro.configs.base import TrainConfig
    from repro.launch import steps as steps_lib
    from repro.launch import train

    cfg = dataclasses.replace(_lm_config(arch, full, "pallas"),
                              bwd_impl="csr")
    step_fn, optimizer = steps_lib.make_train_step(cfg, TrainConfig())
    params = jax.eval_shape(steps_lib.init_fn_for(cfg),
                            jax.random.PRNGKey(0))
    assert_kernels_compiled(
        "train step", step_fn, params,
        jax.eval_shape(optimizer.init, params),
        {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)})

    first = {}
    for impl in ("pallas", "xla"):
        with Phase(f"train.{impl}"):
            _, hist = train.run(arch, full=full, io_impl=impl,
                                bwd_impl="csr", steps=steps, batch=batch,
                                seq=seq, log_every=1)
        losses = [h["loss"] for h in hist]
        check(len(losses) == steps and all(map(math.isfinite, losses)),
              f"train.{impl}: losses {losses}")
        first[impl] = losses[0]
    rel = abs(first["pallas"] - first["xla"]) / abs(first["xla"])
    check(rel <= 1e-2, f"step-1 loss pallas {first['pallas']} vs xla "
          f"{first['xla']} (rel {rel:.3g} > 1e-2)")
    print(f"train: step-1 loss pallas {first['pallas']:.6f} xla "
          f"{first['xla']:.6f} (rel {rel:.3g})", flush=True)


# --------------------------------------------------------------------------
# (d) retrieval
# --------------------------------------------------------------------------

def retrieval_phase(preset=RETRIEVAL, slots=8, requests=16, n_check=4,
                    impl="pallas"):
    from repro import configs
    from repro.core import bloom as bloom_lib
    from repro.launch import serve
    from repro.launch import steps as steps_lib
    from repro.serving import retrieval as retrieval_lib

    rcfg = configs.get_retrieval_config(preset)
    check(rcfg.resolved_impl == impl,
          f"retrieval resolves to {rcfg.resolved_impl!r}, not {impl!r}")
    if impl == "pallas":
        S = jax.ShapeDtypeStruct
        assert_kernels_compiled(
            "retrieval decode step",
            steps_lib.make_retrieval_decode_step(rcfg),
            S((slots, rcfg.m), jnp.float32), S((slots,), jnp.bool_))

    with Phase("retrieval"):
        res, stats = serve.run_retrieval(preset, slots=slots,
                                         requests=requests)
    served = [r for r in res.values()
              if r.done and not r.shed and not r.rejected]
    check(len(served) == requests and stats.rejects == 0,
          f"retrieval: {len(served)}/{requests} served")

    # the pool rows the engine decoded, rebuilt from the same seeded
    # params, against the streaming XLA oracle
    params = retrieval_lib.init_retrieval_params(rcfg)
    program = retrieval_lib.RetrievalProgram(rcfg)
    spec = rcfg.spec()
    with Phase("retrieval.oracle"):
        for r in served[:n_check]:
            (row, _), _ = program.prefill(params, r)
            logp = jax.nn.log_softmax(row.astype(jnp.float32))[None]
            _, ids = bloom_lib.decode_topk(spec, logp, rcfg.topk,
                                           chunk=rcfg.chunk)
            want = [int(i) for i in np.asarray(ids)[0]]
            check(want == r.topk_ids, f"retrieval rid {r.rid}: kernel ids "
                  f"{r.topk_ids} != decode_topk ids {want}")
    print(f"retrieval: {len(served)} requests served on d={rcfg.d:,}; "
          f"top-{rcfg.topk} ids of {min(n_check, len(served))} pool rows "
          f"equal core.bloom.decode_topk", flush=True)


# --------------------------------------------------------------------------
# four chips: the sharded pool
# --------------------------------------------------------------------------

def four_chip_phase(arch=ARCH, full=True, slots_per_host=2,
                    requests_per_host=4, prompt_len=128, gen=32, topk=8):
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import (Engine, LoadSpec, ShardedEngine,
                               merge_workloads, sharded_workload)

    cfg = _lm_config(arch, full, "pallas")
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    mesh = make_serving_mesh()
    n_hosts = mesh.shape["data"]
    spec = LoadSpec(n_requests=requests_per_host, vocab=cfg.vocab, rate=1.0,
                    prompt_lens=(prompt_len // 2, prompt_len),
                    gen_lens=(gen // 4, gen // 2, gen), seed=0)
    max_len = prompt_len + gen

    engine = ShardedEngine(cfg, params, mesh=mesh,
                           slots_per_host=slots_per_host, max_len=max_len,
                           topk=topk, transport="collective")
    with Phase("sharded"):
        res_s, st_s = engine.run(sharded_workload(spec, n_hosts))
    per_dev = [(d.memory_stats() or {}).get("bytes_in_use", "n/a")
               for d in jax.devices()]
    print("bytes_in_use per device: " + ", ".join(
        f"{d.id}:{b}" for d, b in zip(jax.devices(), per_dev)), flush=True)
    pool = engine.program._pool_template
    pool_devs = {s.device for leaf in jax.tree.leaves(pool)
                 for s in leaf.addressable_shards}
    check(len(pool_devs) == n_hosts,
          f"slot pool spans {len(pool_devs)} devices, not {n_hosts}")
    compiles = engine.program._decode._cache_size()
    check(compiles == 1, f"sharded decode step compiled {compiles} times")

    # The reference pool decodes as many rows per step as one shard does.
    # On the TPU, XLA rounds a batch of 8 rows differently from a batch
    # of 2, and random-weight logits are near enough to tied that a
    # last-bit difference flips a later token; per-row results are
    # independent of the co-resident requests, so the schedule may differ.
    single = Engine(cfg, params, n_slots=slots_per_host, max_len=max_len,
                    topk=topk)
    with Phase("single"):
        res_1, st_1 = single.run(
            merge_workloads(sharded_workload(spec, n_hosts)))
    check(set(res_s) == set(res_1), "sharded and single-host served "
          "different request sets")
    check(all(r.done and r.tokens for r in res_s.values()),
          "sharded: not every request was served")
    diff = [rid for rid in res_s if res_s[rid].tokens != res_1[rid].tokens]
    check(not diff, f"sharded vs single-host tokens differ for {diff}")
    print(f"sharded: {len(res_s)} requests on {n_hosts} hosts x "
          f"{slots_per_host} slots, tokens identical to the single-host "
          f"engine, decode step compiled once", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded pool on 4 chips")
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    dev = device_check(n_chips)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    if args.four_chips:
        four_chip_phase()
    else:
        serve_phase()
        train_phase()
        retrieval_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
