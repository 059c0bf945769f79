import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): prove the distribution config is
coherent without hardware.

For every (arch x shape) cell this driver lowers + compiles the real step
function (train step incl. optimizer update / prefill / decode incl. Eq. 3
top-k recovery) against ShapeDtypeStruct stand-ins on the production mesh
(16x16 single pod, 2x16x16 multi-pod) and records:

  * memory_analysis()            — proves the step fits per-device HBM;
  * cost_analysis() FLOPs/bytes  — roofline compute & memory terms;
  * HLO collective parse         — roofline collective term.

Roofline numbers come from two reduced-depth *unrolled* variants (L and 2L
layers; XLA cost analysis counts while-bodies once — see launch/roofline),
extrapolated linearly to full depth; the full-depth scanned model is also
compiled as the fits-and-compiles proof.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all                 # 32-cell single-pod
  python -m repro.launch.dryrun --all --multi-pod     # 512-chip proof
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import SHAPE_BY_NAME, TrainConfig
from repro.launch import roofline, steps
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.launch.sharding import (DistContext, batch_pspecs, cache_pspecs,
                                   opt_state_pspecs, param_pspecs)
from repro.models import transformer as tf
from repro.train import trainer as trainer_lib

KEY_SDS = jax.ShapeDtypeStruct((2,), jnp.uint32)


def _params_sds(cfg, serving: bool = False):
    init = steps.init_fn_for(cfg)
    sds = jax.eval_shape(init, KEY_SDS)
    if serving:  # bf16 serving checkpoint: no fp32 master at inference
        sds = jax.eval_shape(
            lambda p: steps.cast_params_for_compute(p, cfg), sds)
    return sds


def _shardings(dist, specs):
    return jax.tree.map(lambda s: dist.sharding(s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def compile_variant(cfg, shape, dist, tc: TrainConfig, zero: bool = False):
    """Lower + compile one step function; return (compiled, lowered).

    zero=True shards optimizer moments over the data axes (ZeRO-1)."""
    mesh = dist.mesh
    params = _params_sds(cfg, serving=shape.kind != "train")
    pspecs = param_pspecs(cfg, params, dist)
    p_sh = _shardings(dist, pspecs)

    if shape.kind == "train":
        step, optimizer = steps.make_train_step(cfg, tc, dist)
        opt_sds = jax.eval_shape(optimizer.init, params)
        opt_specs = opt_state_pspecs(opt_sds, pspecs,
                                     zero_dist=dist if zero else None,
                                     params_shapes=params)
        opt_sh = _shardings(dist, opt_specs)
        batch = configs.input_specs(cfg, shape)
        b_sh = _shardings(dist, batch_pspecs(cfg, batch, dist))
        with mesh:
            jitted = jax.jit(step, in_shardings=(p_sh, opt_sh, b_sh),
                             out_shardings=(p_sh, opt_sh, None))
            lowered = jitted.lower(params, opt_sds, batch)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        step = steps.make_prefill_step(cfg, dist)
        batch = configs.input_specs(cfg, shape)
        b_sh = _shardings(dist, batch_pspecs(cfg, batch, dist))
        with mesh:
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params, batch)
            compiled = lowered.compile()
    else:  # decode
        step = steps.make_decode_step(cfg, topk=16, dist=dist)
        token = configs.input_specs(cfg, shape)["tokens"]
        caches = configs.cache_specs(cfg, shape)
        c_specs = cache_pspecs(cfg, caches, dist, shape.global_batch)
        c_sh = _shardings(dist, c_specs)
        tok_ax = dist.batch_spec_axes(shape.global_batch)
        t_sh = dist.sharding(P(tok_ax, None))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        with mesh:
            jitted = jax.jit(step,
                             in_shardings=(p_sh, t_sh, c_sh, None))
            lowered = jitted.lower(params, token, caches, pos)
            compiled = lowered.compile()
    return compiled, lowered


def _collect(compiled, n_devices):
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    colls = roofline.parse_collectives(compiled.as_text(), n_devices)
    return {
        "flops_dev": float(cost.get("flops", 0.0)),
        "bytes_dev": float(cost.get("bytes accessed", 0.0)),
        "collectives": colls,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
    }


def _reduced(cfg, n_layers):
    """Depth-reduced, unrolled variant for exact per-layer cost counting."""
    kw = dict(num_layers=n_layers, scan_layers=False,
              unroll_for_analysis=True)
    if cfg.encoder_layers:
        kw["encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             bloom: bool = True, roofline_pass: bool = True,
             overrides=None, out_dir: str = "experiments/dryrun",
             mesh_shape=None, tag: str = "", zero: bool = False,
             optimizer: str = "adamw"):
    """mesh_shape: optional (data, model) override, e.g. (32, 8) for a
    TP=8 hillclimb variant (256 chips either way)."""
    cfg = configs.get_config(arch, bloom=bloom, **(overrides or {}))
    shape = SHAPE_BY_NAME[shape_name]
    ok, reason = configs.cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    if mesh_shape is not None:
        mesh = auto_mesh(mesh_shape,
                         ("data", "model") if len(mesh_shape) == 2
                         else ("pod", "data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    dist = DistContext(mesh)
    n_dev = mesh.size
    tc = TrainConfig(optimizer=optimizer, grad_clip_norm=1.0,
                     warmup_steps=0)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "x".join(map(str, mesh.devices.shape)),
              "bloom": bloom, "n_devices": n_dev,
              "param_count": cfg.param_count(),
              "model_flops_global": roofline.model_flops(cfg, shape)}

    # 1. full-depth scanned compile: the fits-and-compiles proof + memory
    t0 = time.perf_counter()
    compiled, _ = compile_variant(cfg, shape, dist, tc, zero=zero)
    result["full"] = _collect(compiled, n_dev)
    result["full"]["compile_s"] = time.perf_counter() - t0
    del compiled

    # 2. roofline terms via reduced unrolled L/2L extrapolation (single-pod)
    if roofline_pass:
        period = tf.period_of(cfg)
        L1, L2 = period, 2 * period
        ext = {}
        for name, L in (("L1", L1), ("L2", L2)):
            t0 = time.perf_counter()
            c, _ = compile_variant(_reduced(cfg, L), shape, dist, tc,
                                   zero=zero)
            ext[name] = _collect(c, n_dev)
            ext[name]["compile_s"] = time.perf_counter() - t0
            ext[name]["layers"] = L
            del c
        Lf = cfg.num_layers
        def extrap(f):
            a, b = f(ext["L1"]), f(ext["L2"])
            per = (b - a) / (L2 - L1)
            return max(a + per * (Lf - L1), 0.0)
        flops = extrap(lambda e: e["flops_dev"])
        bytes_ = extrap(lambda e: e["bytes_dev"])
        coll = extrap(lambda e: e["collectives"]["total_bytes"])
        result["reduced"] = ext
        result["roofline"] = roofline.roofline_terms(flops, bytes_, coll)
        result["roofline"]["flops_dev"] = flops
        result["roofline"]["bytes_dev"] = bytes_
        result["roofline"]["coll_bytes_dev"] = coll
        mf = result["model_flops_global"] / n_dev
        result["roofline"]["model_flops_ratio"] = (
            mf / flops if flops > 0 else 0.0)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        base_tag = tag or ("multipod" if multi_pod else "singlepod")
        suffix = "" if bloom else "__dense"
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{base_tag}{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        result["artifact"] = path
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-bloom", action="store_true")
    ap.add_argument("--no-roofline", action="store_true",
                    help="full compile proof only (used for multi-pod)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch, shape, ok, _ in configs.all_cells():
            if ok:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    failures = 0
    for arch, shape in cells:
        t0 = time.perf_counter()
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           bloom=not args.no_bloom,
                           roofline_pass=not args.no_roofline,
                           out_dir=args.out)
            if "roofline" in res:
                r = res["roofline"]
                print(f"OK  {arch:18s} {shape:12s} "
                      f"compute={r['compute_s']:.4f}s "
                      f"memory={r['memory_s']:.4f}s "
                      f"coll={r['collective_s']:.4f}s "
                      f"dom={r['dominant']} "
                      f"[{time.perf_counter()-t0:.0f}s]", flush=True)
            else:
                mem = res.get("full", {}).get("memory", {})
                print(f"OK  {arch:18s} {shape:12s} "
                      f"temp={mem.get('temp_bytes', 0)/2**30:.2f}GiB "
                      f"args={mem.get('argument_bytes', 0)/2**30:.2f}GiB "
                      f"[{time.perf_counter()-t0:.0f}s]", flush=True)
        except Exception as e:  # noqa
            failures += 1
            print(f"FAIL {arch} {shape}: {e}", flush=True)
            traceback.print_exc()
    print(f"done: {len(cells) - failures}/{len(cells)} cells passed",
          flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
