"""Serving driver: thin CLI over the continuous-batching engine.

Default mode builds a seeded Poisson workload (serving/loadgen.py) and
runs it through repro.serving.Engine — requests are admitted into freed
cache slots every decode step and retired on per-slot stop conditions,
so a drained slot never burns decode FLOPs while traffic waits.  Every
decode step still runs the paper's Eq. 3 top-k recovery from the m-dim
Bloom softmax back to real vocabulary ids (Fig. 3 right); with
io_impl="pallas" that recovery is the fused decode-topk kernel.

``--static`` keeps the old whole-batch path for A/B: one batch of
identical-length prompts, prefilled together, decoded until the longest
request drains.  That path (run()) also remains the only one serving
enc-dec / frontend-stub archs (whisper, pixtral), whose prefill carries
non-token inputs the engine does not schedule.

``--mode retrieval`` serves one-shot Bloom top-k retrieval requests
(Zipf item lookups over a configs/retrieval.py catalog preset) through
RetrievalEngine — the identical slot loop, so ``--failpoints`` and the
overload flags (``--deadline-slack`` / ``--max-queue-depth``,
DESIGN.md §14) apply there too.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
      --slots 4 --requests 16 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --static \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --mode retrieval \
      --retrieval-config smoke --slots 4 --requests 16 \
      --failpoints 'surge:3@1' --deadline-slack 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_serving_mesh
from repro.launch.sharding import DistContext
from repro.models import encdec as encdec_lib
from repro.models import io as io_lib
from repro.models import transformer as tf
from repro.serving import retrieval as retrieval_lib
from repro.serving import (AdmissionPolicy, Engine, FailPlan, LoadSpec,
                           RetrievalEngine, RetrievalLoadSpec,
                           ShardedEngine, evaluate_retrieval,
                           init_retrieval_params, make_workload,
                           mean_latency, retrieval_workload,
                           sharded_workload)


def pad_caches_to(caches_small, caches_template):
    """Place prefill caches (length S_p) into preallocated max-length
    buffers — the whole-batch special case (slot 0, full batch) of the
    engine's slot-indexed steps.insert_cache_slot."""
    return steps_lib.insert_cache_slot(caches_template, caches_small, 0)


def _config(arch: str, full: bool, io_impl, table_dtype=None):
    cfg = (configs.get_config(arch) if full
           else configs.get_smoke_config(arch))
    import dataclasses
    if io_impl is not None:
        cfg = dataclasses.replace(cfg, io_impl=io_impl)
    if table_dtype is not None:
        cfg = dataclasses.replace(cfg, table_dtype=table_dtype)
    return cfg


def _setup(cfg, seed: int):
    mesh = make_local_mesh()
    dist = DistContext(mesh) if mesh.size > 1 else None
    init = steps_lib.init_fn_for(cfg)
    params = init(jax.random.PRNGKey(seed))
    # one-time cast to the serving dtype (bf16 serving checkpoint)
    params = steps_lib.cast_params_for_compute(params, cfg)
    return params, dist


def _overload_policy(deadline_slack, max_queue_depth):
    """CLI knobs -> optional AdmissionPolicy (DESIGN.md §14): either
    flag alone activates the policy (deadline shedding needs workload
    deadlines; the ladder runs with its default thresholds)."""
    if deadline_slack is None and max_queue_depth is None:
        return None
    return AdmissionPolicy(max_queue_depth=max_queue_depth)


def _tag_deadlines(requests, deadline_slack):
    if deadline_slack is not None:
        for r in requests:
            r.deadline_step = r.arrival_step + deadline_slack
    return requests


def run(arch: str, batch: int = 4, prompt_len: int = 32, gen: int = 16,
        topk: int = 8, seed: int = 0, full: bool = False,
        io_impl: str | None = None, table_dtype: str | None = None):
    """Static whole-batch serving (the --static / A-B baseline path)."""
    cfg = _config(arch, full, io_impl, table_dtype)
    params, dist = _setup(cfg, seed)
    max_len = prompt_len + gen

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, size=(batch, prompt_len),
                           dtype=np.int32)
    batch_in = {"tokens": jnp.asarray(prompts)}
    if cfg.family in ("vlm", "audio"):
        batch_in["embeds"] = jnp.zeros((batch, max(4, prompt_len // 4),
                                        cfg.d_model), jnp.dtype(cfg.dtype))

    prefill = jax.jit(steps_lib.make_prefill_step(cfg, dist))
    decode = jax.jit(steps_lib.make_decode_step(cfg, topk=topk, dist=dist))

    t0 = time.perf_counter()
    pre = prefill(params, batch_in)
    if cfg.family == "audio":
        template = encdec_lib.init_encdec_cache(
            cfg, batch, max_len, batch_in["embeds"].shape[1])
    else:
        template = tf.init_lm_cache(cfg, batch, max_len)
    caches = pad_caches_to(pre["caches"], template)
    t_prefill = time.perf_counter() - t0

    # greedy decode in recovered-vocab space (hash matrix already cached by
    # make_decode_step — no per-step vocab rehash)
    last = pre["last_logits"]
    _, ids = io_lib.recover_topk(cfg, last, topk=topk)
    token = ids[:, :1].astype(jnp.int32)

    n_prefix = prompt_len
    generated = [np.asarray(token)]
    t0 = time.perf_counter()
    for t in range(gen - 1):
        out = decode(params, token, caches, jnp.int32(n_prefix + t))
        caches = out["caches"]
        token = out["topk_ids"][:, :1].astype(jnp.int32)
        generated.append(np.asarray(token))
    t_decode = time.perf_counter() - t0
    gen_tokens = np.concatenate(generated, axis=1)

    print(f"prefill {prompt_len} toks x{batch}: {t_prefill*1e3:.0f} ms")
    print(f"decode  {gen-1} steps: {t_decode*1e3:.0f} ms "
          f"({(gen-1)*batch/max(t_decode,1e-9):.0f} tok/s)")
    print("generated ids (first seq):", gen_tokens[0].tolist())
    return gen_tokens


def run_continuous(arch: str, slots: int = 4, requests: int = 16,
                   rate: float = 1.0, prompt_len: int = 32, gen: int = 16,
                   topk: int = 8, seed: int = 0, full: bool = False,
                   io_impl: str | None = None, eos_id: int | None = None,
                   prefill_workers: int = 1,
                   table_dtype: str | None = None,
                   failpoints: str | None = None,
                   deadline_slack: int | None = None,
                   max_queue_depth: int | None = None):
    """Continuous batching over a seeded Poisson workload."""
    cfg = _config(arch, full, io_impl, table_dtype)
    if not Engine.supports(cfg):       # before paying for param init
        raise SystemExit(
            f"{arch}: enc-dec / frontend-stub archs serve via --static")
    params, dist = _setup(cfg, seed)
    spec = LoadSpec(
        n_requests=requests, vocab=cfg.vocab, rate=rate,
        prompt_lens=(max(prompt_len // 2, 2), prompt_len),
        gen_lens=(max(gen // 4, 1), gen // 2 or 1, gen), seed=seed)
    workload = _tag_deadlines(make_workload(spec), deadline_slack)
    max_len = max(r.prompt_len + r.max_gen for r in workload)

    engine = Engine(cfg, params, n_slots=slots, max_len=max_len,
                    topk=topk, eos_id=eos_id, dist=dist,
                    prefill_workers=prefill_workers,
                    failpoints=FailPlan.parse(failpoints),
                    admission_policy=_overload_policy(deadline_slack,
                                                      max_queue_depth))
    results, stats = engine.run(workload)
    if stats.rejects:
        print(f"rejected {stats.rejects} requests "
              f"(prefill attempts exhausted)")
    if stats.sheds or stats.degrades:
        print(f"overload policy: {stats.sheds} shed, "
              f"{stats.degrades} degrade transitions")

    row = stats.as_row()
    print(f"served {len(results)} requests on {slots} slots: "
          f"{row['decode_steps']} decode steps, "
          f"utilization {row['utilization']:.2f}, "
          f"mean latency {mean_latency(results):.1f} steps")
    print(f"wall {stats.wall_s*1e3:.0f} ms "
          f"({stats.tokens_out/max(stats.wall_s, 1e-9):.0f} tok/s)")
    for r in list(results.values())[:4]:
        print(f"  req {r.rid}: arrive {r.arrival_step} admit "
              f"{r.admitted_step} finish {r.finish_step} "
              f"tokens {r.tokens[:8]}{'...' if len(r.tokens) > 8 else ''}")
    return results, stats


def run_sharded(arch: str, slots_per_host: int = 1, requests: int = 8,
                rate: float = 1.0, prompt_len: int = 32, gen: int = 16,
                topk: int = 8, seed: int = 0, full: bool = False,
                io_impl: str | None = None, eos_id: int | None = None,
                gossip_delay: int = 1, transport: str = "sim",
                prefill_workers: int = 1,
                compact_threshold: float | None = None,
                table_dtype: str | None = None,
                failpoints: str | None = None,
                deadline_slack: int | None = None,
                max_queue_depth: int | None = None):
    """Data-axis-sharded serving over per-host arrival streams.

    One simulated host per `data` shard — run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to simulate an
    8-host topology on CPU (DESIGN.md §8/§9).  `requests` is PER HOST.
    Defaults (sim transport, one prefill worker, no compaction) are
    exactly PR 3's behavior.  ``failpoints`` replays a deterministic
    failure schedule (serving/failpoints.py grammar) against the run,
    e.g. ``kill_host:1@3`` — survivors reclaim the dead host's slots and
    finish every request.
    """
    cfg = _config(arch, full, io_impl, table_dtype)
    if not Engine.supports(cfg):       # before paying for param init
        raise SystemExit(
            f"{arch}: enc-dec / frontend-stub archs serve via --static")
    mesh = make_serving_mesh()
    n_hosts = mesh.shape["data"]
    init = steps_lib.init_fn_for(cfg)
    params = steps_lib.cast_params_for_compute(
        init(jax.random.PRNGKey(seed)), cfg)
    spec = LoadSpec(
        n_requests=requests, vocab=cfg.vocab, rate=rate,
        prompt_lens=(max(prompt_len // 2, 2), prompt_len),
        gen_lens=(max(gen // 4, 1), gen // 2 or 1, gen), seed=seed)
    per_host = sharded_workload(spec, n_hosts)
    for reqs in per_host:
        _tag_deadlines(reqs, deadline_slack)
    max_len = max(r.prompt_len + r.max_gen
                  for reqs in per_host for r in reqs)

    engine = ShardedEngine(cfg, params, mesh=mesh,
                           slots_per_host=slots_per_host, max_len=max_len,
                           topk=topk, eos_id=eos_id,
                           gossip_delay=gossip_delay, transport=transport,
                           prefill_workers=prefill_workers,
                           compact_threshold=compact_threshold,
                           failpoints=FailPlan.parse(failpoints),
                           admission_policy=_overload_policy(
                               deadline_slack, max_queue_depth))
    results, stats = engine.run(per_host)

    row = stats.as_row()
    print(f"served {len(results)} requests on {n_hosts} hosts x "
          f"{slots_per_host} slots (gossip_delay={gossip_delay}, "
          f"transport={transport}, prefill_workers={prefill_workers}, "
          f"compact={compact_threshold}): "
          f"{row['decode_steps']} decode steps, "
          f"{row['compactions']} compactions, "
          f"utilization {row['utilization']:.2f}, "
          f"mean latency {mean_latency(results):.1f} steps")
    if failpoints:
        print(f"failpoints {failpoints!r}: {stats.host_downs} host_downs, "
              f"{stats.requeued} requeued, {stats.rejects} rejects")
    if stats.sheds or stats.degrades:
        print(f"overload policy: {stats.sheds} shed, "
              f"{stats.degrades} degrade transitions")
    print(f"wall {stats.wall_s*1e3:.0f} ms "
          f"({stats.tokens_out/max(stats.wall_s, 1e-9):.0f} tok/s)")
    return results, stats




def run_retrieval(preset: str = "smoke", slots: int = 4,
                  requests: int = 16, rate: float = 2.0, seed: int = 0,
                  prefill_workers: int = 1,
                  failpoints: str | None = None,
                  deadline_slack: int | None = None,
                  max_queue_depth: int | None = None):
    """One-shot Bloom retrieval serving (--mode retrieval): Zipf item
    lookups from ``loadgen.retrieval_workload`` through RetrievalEngine
    — the same ``run_slot_loop`` the LM engine drives, so
    ``--failpoints`` (prefill faults, surge, slow_decode) and the
    overload policy flags work unchanged.  The pool is single-host
    (sharding it is the remaining ROADMAP item), so there is no
    ``--transport`` here."""
    rcfg = configs.get_retrieval_config(preset)
    spec = RetrievalLoadSpec(n_requests=requests, catalog=rcfg.d,
                             c_max=rcfg.c_max, rate=rate, seed=seed)
    workload = _tag_deadlines(retrieval_workload(spec), deadline_slack)
    params = init_retrieval_params(rcfg)
    engine = RetrievalEngine(rcfg, params, n_slots=slots,
                             prefill_workers=prefill_workers,
                             failpoints=FailPlan.parse(failpoints),
                             admission_policy=_overload_policy(
                                 deadline_slack, max_queue_depth))
    results, stats = engine.run(workload)

    row = stats.as_row()
    served = [r for r in results.values() if r.done and not r.shed]
    print(f"served {len(served)}/{len(results)} retrieval requests on "
          f"{slots} slots over a d={rcfg.d:,} catalog ({preset}): "
          f"{row['decode_steps']} decode steps, "
          f"utilization {row['utilization']:.2f}, "
          f"mean latency {mean_latency(results):.1f} steps")
    mb = engine.modeled_bytes
    if mb["streaming_bytes"]:
        print(f"modeled decode HBM bytes: streaming "
              f"{mb['streaming_bytes']:,} vs dense-table oracle "
              f"{mb['dense_oracle_bytes']:,} "
              f"({mb['dense_oracle_bytes']/mb['streaming_bytes']:.1f}x)")
    if stats.rejects:
        print(f"rejected {stats.rejects} requests "
              f"(prefill attempts exhausted)")
    if stats.sheds or stats.degrades:
        print(f"overload policy: {stats.sheds} shed, "
              f"{stats.degrades} degrade transitions")
    if rcfg.d <= retrieval_lib.EVAL_MAX_CATALOG and served:
        metrics = evaluate_retrieval(rcfg, params, served)
        print(f"offline ranking vs held-out targets: "
              f"map {metrics['map']:.4f}, rr {metrics['rr']:.4f} "
              f"over {metrics['n_evaluated']} requests")
    else:
        print("offline ranking eval skipped "
              f"(d={rcfg.d:,} > {retrieval_lib.EVAL_MAX_CATALOG:,}"
              f"{'' if served else ' or nothing served'})")
    return results, stats


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("lm", "retrieval"), default="lm",
                    help="'lm' = token generation (default); 'retrieval' "
                         "= one-shot Bloom top-k over an item catalog "
                         "(DESIGN.md §11; --retrieval-config picks the "
                         "catalog preset, --arch is ignored)")
    ap.add_argument("--retrieval-config",
                    choices=sorted(configs.RETRIEVAL_CONFIGS),
                    default="smoke",
                    help="configs/retrieval.py preset (--mode retrieval)")
    ap.add_argument("--arch", default=None,
                    choices=list(configs.ARCH_NAMES))
    ap.add_argument("--static", action="store_true",
                    help="old whole-batch path (A/B baseline; required "
                         "for enc-dec / frontend archs)")
    ap.add_argument("--sharded", action="store_true",
                    help="data-axis-sharded pool: one simulated host per "
                         "data shard (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--slots-per-host", type=int, default=1,
                    help="cache-pool slots per host shard (--sharded)")
    ap.add_argument("--gossip-delay", type=int, default=1,
                    help="steps before arrivals/releases become globally "
                         "visible (--sharded)")
    ap.add_argument("--transport", choices=("sim", "collective"),
                    default="sim",
                    help="control-plane delta transport (--sharded): "
                         "'sim' = PR-3 in-process gossip (default), "
                         "'collective' = fixed-size padded all_gather "
                         "over the mesh data axis (jax.distributed-ready)")
    ap.add_argument("--prefill-workers", type=int, default=1,
                    help="prefill-pool size: FIFO over N single-device "
                         "mesh slices (default 1 = PR-3 behavior)")
    ap.add_argument("--compact-threshold", type=float, default=None,
                    help="per-host fragmentation (dead-slot fraction "
                         "below the highest live slot) above which the "
                         "slot pool compacts; default off = PR-3 "
                         "behavior (--sharded)")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch size (--static path)")
    ap.add_argument("--slots", type=int, default=4,
                    help="cache-pool slots (continuous path)")
    ap.add_argument("--requests", type=int, default=16,
                    help="workload size (continuous path)")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrivals per decode step")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a slot early on this token id")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--io-impl", choices=("xla", "pallas"), default=None,
                    help="override cfg.io_impl (pallas = fused Bloom "
                         "kernels incl. streaming decode-topk)")
    ap.add_argument("--table-dtype", default=None,
                    choices=("auto", "float32", "bfloat16", "int8",
                             "fp8_e4m3"),
                    help="Bloom table/logp storage dtype (DESIGN.md §13); "
                         "auto = legacy cast-to-activation-dtype; the "
                         "serve path quantizes the embedding table once "
                         "and decodes through narrow logp rows")
    ap.add_argument("--failpoints", default=None,
                    help="deterministic fault schedule "
                         "(serving/failpoints.py grammar), e.g. "
                         "'kill_host:1@3,fail_prefill:2:3,surge:3@1'; "
                         "host kills need --sharded")
    ap.add_argument("--deadline-slack", type=int, default=None,
                    help="tag every request with deadline = arrival + "
                         "SLACK and enable the admission policy: queued "
                         "requests past their deadline are SHED "
                         "deterministically (DESIGN.md §14)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="bound the visible queue per home host; excess "
                         "arrivals are shed FIFO-last (enables the "
                         "admission policy, DESIGN.md §14)")
    args = ap.parse_args()
    if args.mode == "retrieval":
        if args.static or args.sharded:
            raise SystemExit("--mode retrieval is its own serve path: "
                             "drop --static/--sharded (sharding the "
                             "retrieval pool is a ROADMAP item)")
        if args.transport != "sim":
            raise SystemExit("--mode retrieval has no control-plane "
                             "transport: the pool is single-host "
                             "(DESIGN.md §11)")
        run_retrieval(args.retrieval_config, slots=args.slots,
                      requests=args.requests, rate=args.rate,
                      seed=args.seed,
                      prefill_workers=args.prefill_workers,
                      failpoints=args.failpoints,
                      deadline_slack=args.deadline_slack,
                      max_queue_depth=args.max_queue_depth)
        return
    if args.arch is None:
        ap.error("--arch is required with --mode lm")
    if args.static:
        run(args.arch, batch=args.batch, prompt_len=args.prompt_len,
            gen=args.gen, topk=args.topk, seed=args.seed, full=args.full,
            io_impl=args.io_impl, table_dtype=args.table_dtype)
    elif args.sharded:
        run_sharded(args.arch, slots_per_host=args.slots_per_host,
                    requests=args.requests, rate=args.rate,
                    prompt_len=args.prompt_len, gen=args.gen,
                    topk=args.topk, seed=args.seed, full=args.full,
                    io_impl=args.io_impl, eos_id=args.eos_id,
                    gossip_delay=args.gossip_delay,
                    transport=args.transport,
                    prefill_workers=args.prefill_workers,
                    compact_threshold=args.compact_threshold,
                    table_dtype=args.table_dtype,
                    failpoints=args.failpoints,
                    deadline_slack=args.deadline_slack,
                    max_queue_depth=args.max_queue_depth)
    else:
        run_continuous(args.arch, slots=args.slots, requests=args.requests,
                       rate=args.rate, prompt_len=args.prompt_len,
                       gen=args.gen, topk=args.topk, seed=args.seed,
                       full=args.full, io_impl=args.io_impl,
                       eos_id=args.eos_id,
                       prefill_workers=args.prefill_workers,
                       table_dtype=args.table_dtype,
                       failpoints=args.failpoints,
                       deadline_slack=args.deadline_slack,
                       max_queue_depth=args.max_queue_depth)


if __name__ == "__main__":
    main()
