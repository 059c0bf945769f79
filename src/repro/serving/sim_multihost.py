"""Multi-host serving simulation driver (runs in its OWN process).

Forces an 8-device CPU topology via XLA_FLAGS *before* jax initializes —
that is why this module must run as ``__main__`` in a fresh process (the
test suite's parent process must keep seeing 1 CPU device, see
tests/conftest.py) — then serves the same seeded per-host workload
through the full control/data-plane matrix (DESIGN.md §9) and dumps
everything a verdict needs as JSON:

  * ``runs`` — ONE ShardedEngine (single jitted decode step, prefill pool
    of 2 mesh-slice workers) driven through
    {sim, collective} transports x {no-compaction, compaction}: the
    collective runs exchange deltas over a REAL device all_gather on the
    8-device topology, and the compaction runs remap the sharded cache
    pytree mid-flight;
  * ``single`` — the PR-2 single-host Engine over the merged workload;
  * ``solo``   — each request alone through static serving (the paper's
    Fig. 3 serving path, the ground truth everything must match
    BIT-identically);
  * ``sims``   — the model-free ``simulate_sharded_schedule`` replays
    (per compaction setting): the engine logs must equal them
    integer-for-integer, COMPACT events included.

Also recorded: per-host event logs (linearization), the decode-step
compile count across the WHOLE matrix (the single-compiled-step
invariant must survive transports and compaction), and the prefill
pool's dispatch stats.

``chaos`` — the host-failure recovery drill (DESIGN.md §10): a 4-host
mesh on the same 8-device topology serves the seeded workload while a
committed ``FailPlan`` kills one host mid-traffic.  Engine runs through
BOTH transports plus the model-free sim replay of the same plan, and
``_verify_chaos`` asserts *in this process* (so the CI chaos job fails
loudly, not just the pytest wrapper): every request completes, recovered
tokens are BIT-identical to the fault-free twin, re-admissions preserve
FIFO order, the engine log equals the sim log integer-for-integer
(RECLAIM / HOST_DOWN events included), the slot log replays soundly, the
drill actually requeued work (non-vacuous), and decode still compiled
exactly once across the fault-free + kill runs (the dead range is an
active-mask change, not a new executable).  ``--failpoints`` overrides
the committed schedule.

Usage:  python -m repro.serving.sim_multihost --out report.json
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json

import jax

from repro import configs
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_serving_mesh
from repro.serving import (AdmissionPolicy, Engine, FailPlan, LoadSpec,
                           ShardedEngine, merge_workloads,
                           overload_workload, sharded_workload,
                           simulate_sharded_schedule, slo_attainment)
from repro.serving.control import replay_slot_log
from repro.serving.loadgen import arrival_span

ARCH = "qwen1.5-0.5b"
N_HOSTS = 8
SLOTS_PER_HOST = 2        # >= 2 so per-host fragmentation can occur
MAX_LEN = 40
TOPK = 4
GOSSIP_DELAY = 1
PREFILL_WORKERS = 2
COMPACT_THRESHOLD = 0.25  # frag 0.5 (1 hole of 2 slots) crosses it

# -- chaos drill (committed schedule; --failpoints overrides) -----------
CHAOS_N_HOSTS = 4         # 4 of the 8 forced devices; kill 1 of 4 hosts
CHAOS_KILL_HOST = 1
CHAOS_KILL_STEP = 3       # inside arrival_span at seed 0: reclaims 2
CHAOS_FAILPOINTS = f"kill_host:{CHAOS_KILL_HOST}@{CHAOS_KILL_STEP}"

# -- overload drill (DESIGN.md §14; --overload-failpoints overrides) ----
# arrivals triple-compressed from step 1 on + every decode step costing
# 3 clock ticks from step 2 on: sustained arrival rate far above pool
# throughput, so the deadline/bounded-queue policy MUST shed and the
# pressure ladder MUST degrade and restore for the drill to pass
OVERLOAD_FAILPOINTS = "surge:3@1,slow_decode:3@2"
OVERLOAD_DEADLINE_SLACK = 8     # SLO: admitted within 8 clock ticks
OVERLOAD_SURGE_START = 1        # workload-level ramp (overload_workload)
OVERLOAD_SURGE_FACTOR = 2
# thresholds sized to the bounded queue: max_queue_depth=2 over 4 homes
# caps post-shed pending at 8 = the pool's 8 slots, so pressure tops out
# near 1.0 — the ladder trips at 2 queued (0.25) / 4 queued (0.5) and
# restores only once the queue is empty for a full window
OVERLOAD_POLICY = AdmissionPolicy(max_queue_depth=2, pressure_window=2,
                                  degrade_lo=0.25, degrade_hi=0.5,
                                  restore_below=0.1)


def _log_of(sched) -> dict:
    return {
        "admissions": sched.admissions,
        "releases": sched.releases,
        "compactions": [(step, list(perm), seq)
                        for step, perm, seq in sched.compactions],
        "rejects": sched.rejects,
        "reclaims": sched.reclaims,
        "host_downs": sched.host_downs,
        "sheds": sched.sheds,
        "degrades": sched.degrades,
        "per_host": [{"admissions": h.admissions,
                      "releases": h.releases,
                      "compactions": [(s, list(p), q)
                                      for s, p, q in h.compactions],
                      "rejects": h.rejects,
                      "reclaims": h.reclaims,
                      "sheds": h.sheds}
                     for h in sched.hosts],
    }


def run(seed: int = 0) -> dict:
    cfg = configs.get_smoke_config(ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    # two requests per host per stream keeps the sim fast on CPU CI while
    # still exercising cross-host admission, mid-flight churn, and enough
    # slot fragmentation for the compaction runs to actually compact
    spec = LoadSpec(n_requests=2, vocab=cfg.vocab, rate=1.0,
                    prompt_lens=(6, 10), gen_lens=(3, 6, 12), seed=seed)

    mesh = make_serving_mesh()
    engine = ShardedEngine(cfg, params, mesh=mesh,
                           slots_per_host=SLOTS_PER_HOST, max_len=MAX_LEN,
                           topk=TOPK, gossip_delay=GOSSIP_DELAY,
                           prefill_workers=PREFILL_WORKERS)

    runs = {}
    for tname in ("sim", "collective"):
        for cname, thresh in (("plain", None),
                              ("compact", COMPACT_THRESHOLD)):
            res, stats = engine.run(sharded_workload(spec, N_HOSTS),
                                    transport=tname,
                                    compact_threshold=thresh)
            runs[f"{tname}_{cname}"] = {
                "tokens": {r.rid: r.tokens for r in res.values()},
                "done": {rid: r.done for rid, r in res.items()},
                "stats": stats.as_row(),
                "log": _log_of(engine._sched),
            }

    sims = {}
    for cname, thresh in (("plain", None), ("compact", COMPACT_THRESHOLD)):
        sim_sched, sim_stats = simulate_sharded_schedule(
            sharded_workload(spec, N_HOSTS), SLOTS_PER_HOST, GOSSIP_DELAY,
            compact_threshold=thresh)
        sims[cname] = {"stats": sim_stats.as_row(),
                       "log": _log_of(sim_sched)}

    single = Engine(cfg, params, n_slots=N_HOSTS * SLOTS_PER_HOST,
                    max_len=MAX_LEN, topk=TOPK)
    single_res, single_stats = single.run(
        merge_workloads(sharded_workload(spec, N_HOSTS)))

    solo = Engine(cfg, params, n_slots=1, max_len=MAX_LEN, topk=TOPK)
    solo_tokens = {}
    for reqs in sharded_workload(spec, N_HOSTS):
        for req in reqs:
            req.arrival_step = 0
            r, _ = solo.run_static([req])
            solo_tokens[req.rid] = r[req.rid].tokens

    return {
        "n_devices": jax.device_count(),
        "n_hosts": N_HOSTS,
        "slots_per_host": SLOTS_PER_HOST,
        "gossip_delay": GOSSIP_DELAY,
        "compact_threshold": COMPACT_THRESHOLD,
        "prefill_workers": PREFILL_WORKERS,
        # compile count across the ENTIRE matrix: 4 engine runs through
        # both transports, with and without mid-flight cache remaps
        "decode_compiles": engine.program._decode._cache_size(),
        "prefill_stats": engine.prefill_pool.stats,
        "runs": runs,
        "sims": sims,
        "single": {"tokens": {r.rid: r.tokens
                              for r in single_res.values()},
                   "stats": single_stats.as_row()},
        "solo": solo_tokens,
    }


def _verify_chaos(chaos: dict, arrival_key: dict) -> None:
    """Hard asserts on the recovery drill — run in THIS process so the
    CI chaos job fails on its own, without the pytest wrapper.
    `arrival_key[rid]` is the original (arrival_step, home, rid) FIFO
    key of each request."""
    base = chaos["base"]
    assert all(base["done"].values()), "fault-free twin did not finish"
    for tname in ("sim", "collective"):
        kr = chaos["kill_runs"][tname]
        # 1. no request lost or rejected under a pure kill plan
        assert all(kr["done"].values()), f"{tname}: lost requests"
        assert kr["stats"]["rejects"] == 0, f"{tname}: spurious rejects"
        # 2. the drill is non-vacuous: the kill reclaimed live work
        assert kr["stats"]["host_downs"] == 1, f"{tname}: no HOST_DOWN"
        assert kr["stats"]["requeued"] >= 1, (
            f"{tname}: kill at step {chaos['kill_step']} reclaimed "
            "nothing — move it inside the arrival span")
        # 3. recovered tokens are BIT-identical to the fault-free twin
        #    (greedy decode is pure in the prompt, so a re-prefilled
        #    request regenerates its exact stream)
        assert kr["tokens"] == base["tokens"], f"{tname}: token drift"
        # 4. re-admissions preserve FIFO order among requeued requests:
        #    each reclaimed rid's LAST admission is its re-admission;
        #    within one HOST_DOWN wave the re-admissions must follow the
        #    original (arrival_step, home, rid) keys (custom --failpoints
        #    plans may kill several hosts at different steps — no global
        #    order exists across waves)
        last_adm = {}
        wave = {}                      # rid -> its LAST reclaim step
        for step, _, rid, _ in kr["log"]["reclaims"]:
            wave[rid] = step
        for _, _, rid, seq in kr["log"]["admissions"]:
            if rid in wave:
                last_adm[rid] = seq
        assert set(last_adm) == set(wave), (
            f"{tname}: reclaimed request never re-admitted")
        for w in set(wave.values()):
            order = sorted((rid for rid, s in wave.items() if s == w),
                           key=last_adm.get)
            keys = [arrival_key[rid] for rid in order]
            assert keys == sorted(keys), (
                f"{tname}: re-admissions out of FIFO order: {order}")
        # 5. slot log replays soundly with RECLAIM events
        replay_slot_log(kr["log"]["admissions"], kr["log"]["releases"],
                        [(s, list(p), q) for s, p, q
                         in kr["log"]["compactions"]],
                        chaos["n_hosts"] * chaos["slots_per_host"],
                        rejects=kr["log"]["rejects"],
                        reclaims=kr["log"]["reclaims"])
    # 6. engine log == model-free sim log, integer-for-integer
    assert chaos["kill_runs"]["sim"]["log"] == chaos["kill_sim"]["log"], \
        "engine/sim log divergence under kill"
    assert (chaos["kill_runs"]["collective"]["log"]
            == chaos["kill_sim"]["log"]), \
        "collective transport log divergence under kill"
    # 7. ONE compiled decode step across fault-free + both kill runs:
    #    host death is an active-mask change, never a new executable
    assert chaos["decode_compiles"] == 1, (
        f"decode recompiled under host death: "
        f"{chaos['decode_compiles']} executables")


def run_chaos(seed: int = 0, failpoints: str | None = None) -> dict:
    spec_str = CHAOS_FAILPOINTS if failpoints is None else failpoints
    plan = FailPlan.parse(spec_str)
    cfg = configs.get_smoke_config(ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    spec = LoadSpec(n_requests=2, vocab=cfg.vocab, rate=1.0,
                    prompt_lens=(6, 10), gen_lens=(3, 6, 12), seed=seed)

    def wl():
        return sharded_workload(spec, CHAOS_N_HOSTS)

    first, last = arrival_span(wl())
    arrival_key = {r.rid: (r.arrival_step, r.home, r.rid)
                   for reqs in wl() for r in reqs}

    mesh = make_serving_mesh(n_hosts=CHAOS_N_HOSTS)
    engine = ShardedEngine(cfg, params, mesh=mesh,
                           slots_per_host=SLOTS_PER_HOST, max_len=MAX_LEN,
                           topk=TOPK, gossip_delay=GOSSIP_DELAY,
                           prefill_workers=PREFILL_WORKERS)

    def pack(res, stats, sched) -> dict:
        return {
            "tokens": {r.rid: r.tokens for r in res.values()},
            "done": {rid: r.done for rid, r in res.items()},
            "stats": {**stats.as_row(), "host_downs": stats.host_downs,
                      "requeued": stats.requeued,
                      "rejects": stats.rejects},
            "log": _log_of(sched),
        }

    base_res, base_stats = engine.run(wl(), transport="sim",
                                      failpoints=None)
    base = pack(base_res, base_stats, engine._sched)

    kill_runs = {}
    for tname in ("sim", "collective"):
        res, stats = engine.run(wl(), transport=tname, failpoints=plan)
        kill_runs[tname] = pack(res, stats, engine._sched)

    kill_sim_sched, kill_sim_stats = simulate_sharded_schedule(
        wl(), SLOTS_PER_HOST, GOSSIP_DELAY, failpoints=plan)
    kill_sim = {"stats": {**kill_sim_stats.as_row(),
                          "host_downs": kill_sim_stats.host_downs,
                          "requeued": kill_sim_stats.requeued,
                          "rejects": kill_sim_stats.rejects},
                "log": _log_of(kill_sim_sched)}

    chaos = {
        "failpoints": spec_str,
        "kill_step": plan.kill_steps()[0] if plan.kill_steps() else None,
        "arrival_span": [first, last],
        "n_hosts": CHAOS_N_HOSTS,
        "slots_per_host": SLOTS_PER_HOST,
        "gossip_delay": GOSSIP_DELAY,
        "decode_compiles": engine.program._decode._cache_size(),
        "base": base,
        "kill_runs": kill_runs,
        "kill_sim": kill_sim,
    }
    if plan.kill_steps():          # custom plans may inject other faults
        _verify_chaos(chaos, arrival_key)
        chaos["verified"] = True
    return chaos


def _verify_overload(ov: dict) -> None:
    """Hard asserts on the overload drill (DESIGN.md §14), in THIS
    process so the CI chaos job fails loudly on its own: under the
    injected surge every request either completes BIT-identically to
    the unloaded twin or is shed deterministically — never both — the
    shed set is identical across SimTransport / CollectiveTransport /
    the model-free sim, the degrade ladder escalated AND restored with
    zero recompiles, and SLO attainment is the pure arithmetic of the
    shed count."""
    base = ov["base"]
    assert all(base["done"].values()), "unloaded twin did not finish"
    assert base["stats"]["sheds"] == 0, "unloaded twin shed requests"
    n_total = len(base["done"])
    shed_sets = {}
    for tname in ("sim", "collective"):
        sr = ov["surge_runs"][tname]
        shed = set(sr["shed_rids"])
        served = {rid for rid, d in sr["done"].items() if d} - shed
        shed_sets[tname] = shed
        # 1. the drill is non-vacuous and clean: sheds happened, no
        #    rejects (the plan injects no prefill faults), every request
        #    reached a terminal state
        assert sr["stats"]["sheds"] > 0, f"{tname}: surge shed nothing"
        assert sr["stats"]["rejects"] == 0, f"{tname}: spurious rejects"
        assert served | shed == set(sr["done"]), (
            f"{tname}: request neither served nor shed")
        # 2. no request is both served and shed
        assert not (served & shed), (
            f"{tname}: shed AND completed: {sorted(served & shed)}")
        # 3. every served request's tokens are BIT-identical to the
        #    unloaded twin's (degradation narrows the served top-k; the
        #    next token is the top-1 id, invariant under the width)
        for rid in served:
            assert sr["tokens"][rid] == base["tokens"][rid], (
                f"{tname}: rid {rid} token drift under overload")
        # 4. the ladder moved both ways: at least one DEGRADE escalation
        #    and one RESTORE once the shed+drained queue released the
        #    pressure (hysteresis means this is a real recovery, not a
        #    flap)
        degr = sr["log"]["degrades"]
        assert any(new > old for _, old, new, _ in degr), (
            f"{tname}: pressure never degraded the pool")
        assert any(new < old for _, old, new, _ in degr), (
            f"{tname}: pool never restored after the surge drained")
        # 5. SLO attainment is the pure arithmetic of the shed count —
        #    tie the result-marked shed flags to run_schedule's
        #    independently drained counter
        assert sr["stats"]["sheds"] == len(shed), (
            f"{tname}: stats.sheds != marked shed requests")
        assert sr["slo_attainment"] == slo_attainment(
            n_total - sr["stats"]["sheds"], n_total)
        # 6. the slot log replays soundly (sheds vacate no slot, so the
        #    replay contract is unchanged)
        replay_slot_log(sr["log"]["admissions"], sr["log"]["releases"],
                        [(s, list(p), q) for s, p, q
                         in sr["log"]["compactions"]],
                        ov["n_hosts"] * ov["slots_per_host"],
                        rejects=sr["log"]["rejects"],
                        reclaims=sr["log"]["reclaims"])
    # 7. shed decisions are deterministic and transport-invariant
    assert shed_sets["sim"] == shed_sets["collective"], (
        "shed set differs between transports")
    assert shed_sets["sim"] == set(ov["surge_sim"]["shed_rids"]), (
        "engine shed set differs from the model-free sim")
    # 8. engine log == model-free sim log, SHED / DEGRADE included
    assert ov["surge_runs"]["sim"]["log"] == ov["surge_sim"]["log"], \
        "engine/sim log divergence under overload"
    assert (ov["surge_runs"]["collective"]["log"]
            == ov["surge_sim"]["log"]), \
        "collective transport log divergence under overload"
    # 9. zero recompiles through every DEGRADE/RESTORE: each pre-built
    #    stage executable compiled at most once across twin + both surge
    #    runs, and every stage the ladder entered compiled exactly once
    entered = {0} | {new for _, _, new, _
                     in ov["surge_runs"]["sim"]["log"]["degrades"]}
    for st, n in ov["stage_decode_compiles"].items():
        assert n <= 1, (
            f"stage {st} decode recompiled: {n} executables")
        if int(st) in entered:
            assert n == 1, f"stage {st} entered but never compiled?"


def run_overload(seed: int = 0, failpoints: str | None = None) -> dict:
    """The overload chaos drill: the seeded per-host workload (ramped,
    deadline-tagged — loadgen.overload_workload) served on a 4-host mesh
    under an injected arrival surge + decode slowdown, with the
    committed AdmissionPolicy shedding and degrading; the unloaded twin
    serves the identical workload with no injection and no policy."""
    spec_str = OVERLOAD_FAILPOINTS if failpoints is None else failpoints
    plan = FailPlan.parse(spec_str)
    cfg = configs.get_smoke_config(ARCH)
    params = steps_lib.cast_params_for_compute(
        steps_lib.init_fn_for(cfg)(jax.random.PRNGKey(0)), cfg)
    spec = LoadSpec(n_requests=3, vocab=cfg.vocab, rate=1.0,
                    prompt_lens=(6, 10), gen_lens=(3, 6, 12), seed=seed)

    def wl():
        return overload_workload(
            spec, CHAOS_N_HOSTS, surge_start=OVERLOAD_SURGE_START,
            surge_factor=OVERLOAD_SURGE_FACTOR,
            deadline_slack=OVERLOAD_DEADLINE_SLACK)

    mesh = make_serving_mesh(n_hosts=CHAOS_N_HOSTS)
    engine = ShardedEngine(cfg, params, mesh=mesh,
                           slots_per_host=SLOTS_PER_HOST, max_len=MAX_LEN,
                           topk=TOPK, gossip_delay=GOSSIP_DELAY,
                           prefill_workers=PREFILL_WORKERS,
                           admission_policy=OVERLOAD_POLICY)
    n_total = CHAOS_N_HOSTS * spec.n_requests

    def pack(res, stats, sched) -> dict:
        shed = sorted(r.rid for r in res.values() if r.shed)
        return {
            "tokens": {r.rid: r.tokens for r in res.values()},
            "done": {rid: r.done for rid, r in res.items()},
            "shed_rids": shed,
            "slo_attainment": slo_attainment(n_total - len(shed),
                                             n_total),
            "stats": {**stats.as_row(), "sheds": stats.sheds,
                      "degrades": stats.degrades,
                      "rejects": stats.rejects},
            "log": _log_of(sched),
        }

    # the unloaded twin: same workload, no injection, no policy — every
    # request serves to completion at full width
    base_res, base_stats = engine.run(wl(), transport="sim",
                                      failpoints=None,
                                      admission_policy=None)
    base = pack(base_res, base_stats, engine._sched)

    surge_runs = {}
    for tname in ("sim", "collective"):
        res, stats = engine.run(wl(), transport=tname, failpoints=plan)
        surge_runs[tname] = pack(res, stats, engine._sched)

    sim_sched, sim_stats = simulate_sharded_schedule(
        wl(), SLOTS_PER_HOST, GOSSIP_DELAY, failpoints=plan,
        admission_policy=OVERLOAD_POLICY)
    shed_sim = sorted(rid for _, rid, _, _ in sim_sched.log.sheds)
    surge_sim = {"shed_rids": shed_sim,
                 "stats": {**sim_stats.as_row(),
                           "sheds": sim_stats.sheds,
                           "degrades": sim_stats.degrades,
                           "rejects": sim_stats.rejects},
                 "log": _log_of(sim_sched)}

    # stage -> compile count (stages sharing one width share one jit; a
    # shared jit reports the same count for each of its stages)
    stage_compiles = {st: jit._cache_size()
                      for st, jit in engine.program._stage_decodes.items()}

    overload = {
        "failpoints": spec_str,
        "overload_steps": plan.overload_steps(),
        "policy": {"max_queue_depth": OVERLOAD_POLICY.max_queue_depth,
                   "pressure_window": OVERLOAD_POLICY.pressure_window,
                   "degrade_lo": OVERLOAD_POLICY.degrade_lo,
                   "degrade_hi": OVERLOAD_POLICY.degrade_hi,
                   "restore_below": OVERLOAD_POLICY.restore_below,
                   "degraded_topk": OVERLOAD_POLICY.degraded_topk},
        "deadline_slack": OVERLOAD_DEADLINE_SLACK,
        "n_hosts": CHAOS_N_HOSTS,
        "slots_per_host": SLOTS_PER_HOST,
        "gossip_delay": GOSSIP_DELAY,
        "n_requests": n_total,
        "stage_decode_compiles": stage_compiles,
        "base": base,
        "surge_runs": surge_runs,
        "surge_sim": surge_sim,
    }
    if plan.overload_steps():      # custom plans may not inject overload
        _verify_overload(overload)
        overload["verified"] = True
    return overload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True, help="JSON report path")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--failpoints", default=None,
                    help="chaos failpoint spec (default: "
                         f"{CHAOS_FAILPOINTS!r})")
    ap.add_argument("--overload-failpoints", default=None,
                    help="overload drill spec (default: "
                         f"{OVERLOAD_FAILPOINTS!r})")
    args = ap.parse_args()
    report = run(seed=args.seed)
    report["chaos"] = run_chaos(seed=args.seed,
                                failpoints=args.failpoints)
    report["overload"] = run_overload(seed=args.seed,
                                      failpoints=args.overload_failpoints)
    with open(args.out, "w") as f:
        json.dump(report, f)
    print("wrote", args.out)
    print("chaos: verified" if report["chaos"].get("verified")
          else "chaos: ran (no kill in plan — checks skipped)")
    print("overload: verified" if report["overload"].get("verified")
          else "overload: ran (no surge in plan — checks skipped)")


if __name__ == "__main__":
    main()
