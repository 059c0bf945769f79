"""Deterministic multi-host serving simulation tests (DESIGN.md §8/§9).

The heavyweight piece runs ``repro.serving.sim_multihost`` in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` —
the forced topology must be set before jax initializes, and this pytest
process must keep seeing 1 CPU device (tests/test_launch.py asserts it).
The driver serves the same seeded per-host workload through the FULL
control/data-plane matrix — {sim, collective} transports x
{no-compaction, compaction} on ONE sharded engine — plus the single-host
engine and solo static serving, and the assertions here prove:

  * per-request tokens are BIT-identical across ALL SIX paths — data-axis
    sharding, transported admission (including the real device all_gather
    of the collective transport), the prefill pool, and mid-flight slot
    compaction change the schedule but never a single recovered token;
  * each engine run's event log equals the model-free
    ``simulate_sharded_schedule`` replay integer-for-integer, COMPACT
    events included, and the sim/collective transports produce identical
    logs (transport equivalence on the device topology);
  * no slot is double-claimed (shared ``replay_slot_log`` through any
    COMPACT remaps) and the merged log is a linearization of per-host
    logs;
  * the single-compiled-step invariant survives the whole matrix (decode
    compiled exactly once across all four runs);
  * the compaction runs actually compact, and the prefill pool actually
    dispatches over both workers.

The JAX-free tests below the subprocess fixture pin the loadgen and
scheduler determinism contracts in-process — including deterministic
(no-hypothesis) versions of the transport-equivalence and compaction
invariants, so they run even where hypothesis is absent.
"""
import json
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO_ROOT, subprocess_env

from repro.serving import (AdmissionPolicy, CollectiveTransport, FailPlan,
                           LoadSpec, ReplicaDivergence, Request,
                           TransportTimeout, host_stream, merge_workloads,
                           overload_workload, replay_slot_log,
                           sharded_workload, simulate_sharded_schedule,
                           slo_attainment)

N_HOSTS = 8
SLOTS_PER_HOST = 2
RUNS = ("sim_plain", "sim_compact", "collective_plain",
        "collective_compact")


@pytest.fixture(scope="module")
def report(tmp_path_factory):
    """One subprocess run of the 8-device sim, shared by the tests."""
    out = tmp_path_factory.mktemp("multihost") / "report.json"
    env = subprocess_env()
    # the driver appends the forced-topology flag itself; wiping any
    # inherited XLA_FLAGS keeps the 8-device count authoritative
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.serving.sim_multihost",
         "--out", str(out)],
        capture_output=True, text=True, env=env,
        cwd=REPO_ROOT, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        return json.load(f)


def test_sim_ran_on_8_devices(report):
    assert report["n_devices"] == 8
    assert report["n_hosts"] == N_HOSTS
    assert report["slots_per_host"] == SLOTS_PER_HOST
    assert set(report["runs"]) == set(RUNS)


def test_tokens_bit_identical_across_all_paths(report):
    """{sim, collective} x {plain, compact} == single-host pool == solo
    static, token for token."""
    solo = report["solo"]
    assert solo, "solo run produced no results"
    single = report["single"]["tokens"]
    assert set(single) == set(solo)
    for rid in solo:
        assert single[rid] == solo[rid], (
            f"req {rid}: single {single[rid]} != solo {solo[rid]}")
    for name in RUNS:
        toks = report["runs"][name]["tokens"]
        assert set(toks) == set(solo)
        for rid in solo:
            assert toks[rid] == solo[rid], (
                f"req {rid}: {name} {toks[rid]} != solo {solo[rid]}")


def test_every_request_completes(report):
    for name in RUNS:
        done = report["runs"][name]["done"]
        assert done and all(done.values()), name


def test_single_compiled_decode_step_survives_the_matrix(report):
    """One executable across sim+collective transports AND mid-flight
    cache compactions (out_specs == pool specs pins the layout)."""
    assert report["decode_compiles"] == 1


def test_engine_logs_match_model_free_simulation(report):
    """Each engine run's transported schedule is exactly the JAX-free
    replay for its compaction setting — scheduling is decoupled from the
    model (the workload has no EOS) — and COMPACT events replay too."""
    as_tuples = lambda evs: [tuple(e) for e in evs]
    as_comp = lambda evs: [(s, tuple(p), q) for s, p, q in evs]
    for name in RUNS:
        sim = report["sims"][name.split("_")[1]]
        run_log, sim_log = report["runs"][name]["log"], sim["log"]
        assert as_tuples(run_log["admissions"]) == \
            as_tuples(sim_log["admissions"]), name
        assert as_tuples(run_log["releases"]) == \
            as_tuples(sim_log["releases"]), name
        assert as_comp(run_log["compactions"]) == \
            as_comp(sim_log["compactions"]), name
        assert report["runs"][name]["stats"]["decode_steps"] == \
            sim["stats"]["decode_steps"]


def test_transport_equivalence_on_device_topology(report):
    """The collective transport (REAL device all_gather on the 8-device
    mesh) reproduces the simulated gossip's log integer-for-integer."""
    for cname in ("plain", "compact"):
        a = report["runs"][f"sim_{cname}"]["log"]
        b = report["runs"][f"collective_{cname}"]["log"]
        assert a == b, f"sim vs collective diverged ({cname})"


def test_compaction_runs_compact_and_stay_schedule_invariant(report):
    """The compact runs execute COMPACT events; the remap moves slot ids
    but never admission/release steps or rids."""
    for t in ("sim", "collective"):
        plain = report["runs"][f"{t}_plain"]
        comp = report["runs"][f"{t}_compact"]
        assert comp["stats"]["compactions"] > 0
        assert len(comp["log"]["compactions"]) == \
            comp["stats"]["compactions"]
        assert plain["stats"]["compactions"] == 0
        key = lambda evs: [(e[0], e[2]) for e in evs]   # (step, rid)
        assert key(plain["log"]["admissions"]) == \
            key(comp["log"]["admissions"])
        # intra-step release order follows slot order, which the remap
        # permutes — per-step multiset comparison
        assert sorted(key(plain["log"]["releases"])) == \
            sorted(key(comp["log"]["releases"]))
        assert plain["stats"]["decode_steps"] == \
            comp["stats"]["decode_steps"]


def test_prefill_pool_dispatches_over_all_workers(report):
    """FIFO pool over 2 mesh-slice workers: every job dispatched, both
    workers used, totals consistent across the 4-run matrix."""
    st = report["prefill_stats"]
    total = sum(r["stats"]["prefills"] for r in report["runs"].values())
    assert st["jobs"] == total
    assert len(st["per_worker"]) == report["prefill_workers"] == 2
    assert sum(st["per_worker"]) == st["jobs"]
    assert all(c > 0 for c in st["per_worker"])


def test_no_slot_double_claim_and_linearization(report):
    """Merged-log soundness through COMPACT remaps (shared
    ``replay_slot_log``), every request admitted exactly once by exactly
    one host, and the merged log restricted to each host's slot range
    reproduces that host's local log exactly (linearization)."""
    n_slots = N_HOSTS * SLOTS_PER_HOST
    for name in RUNS:
        log = report["runs"][name]["log"]
        adm = [tuple(e) for e in log["admissions"]]
        rel = [tuple(e) for e in log["releases"]]
        comp = [(s, tuple(p), q) for s, p, q in log["compactions"]]
        final = replay_slot_log(adm, rel, comp, n_slots)
        assert all(o is None for o in final), f"{name}: slots left live"

        # every request admitted exactly once, by exactly one host —
        # "which host" is the admitting slot's owner at admission time
        rids = [rid for _, _, rid, _ in adm]
        assert len(rids) == len(set(rids))

        for h, hlog in enumerate(log["per_host"]):
            lo, hi = h * SLOTS_PER_HOST, (h + 1) * SLOTS_PER_HOST
            assert [tuple(e) for e in hlog["admissions"]] == \
                [e for e in adm if lo <= e[1] < hi]
            assert [tuple(e) for e in hlog["releases"]] == \
                [e for e in rel if lo <= e[1] < hi]
            assert [(s, tuple(p), q)
                    for s, p, q in hlog["compactions"]] == \
                [(s, p[lo:hi], q) for s, p, q in comp
                 if p[lo:hi] != tuple(range(lo, hi))]
        # seqs strictly increase within each host list (order preserved)
        # and never collide across a host's lists
        for hlog in log["per_host"]:
            for evs in (hlog["admissions"], hlog["releases"],
                        hlog["compactions"]):
                assert [e[-1] for e in evs] == \
                    sorted(e[-1] for e in evs)
            seqs = [e[-1] for e in hlog["admissions"] + hlog["releases"]
                    + hlog["compactions"]]
            assert len(seqs) == len(set(seqs))


# ---------------------------------------------------------------------------
# JAX-free determinism contracts (loadgen + scheduler) — run in-process
# ---------------------------------------------------------------------------

def test_host_stream_is_pure_in_seed_and_host():
    """satellite: arrivals are a pure function of (seed, host_id) — the
    stream does not depend on which hosts were drawn before it."""
    spec = LoadSpec(n_requests=6, vocab=256, rate=0.8, seed=11)
    alone = host_stream(spec, host=3, n_hosts=8)
    in_full_draw = sharded_workload(spec, 8)[3]
    assert [r.rid for r in alone] == [r.rid for r in in_full_draw]
    assert [r.arrival_step for r in alone] == \
        [r.arrival_step for r in in_full_draw]
    assert [r.max_gen for r in alone] == [r.max_gen for r in in_full_draw]
    assert all((x.prompt == y.prompt).all()
               for x, y in zip(alone, in_full_draw))
    # distinct hosts get distinct streams (same seed)
    other = host_stream(spec, host=4, n_hosts=8)
    assert [r.arrival_step for r in other] != \
        [r.arrival_step for r in alone] or \
        any((x.prompt != y.prompt).any() for x, y in zip(other, alone))
    # rids are globally unique and host-tagged
    all_rids = [r.rid for reqs in sharded_workload(spec, 8) for r in reqs]
    assert len(all_rids) == len(set(all_rids))
    assert all(r.home == h for h, reqs in
               enumerate(sharded_workload(spec, 8)) for r in reqs)


def test_two_sharded_runs_replay_identical_event_logs():
    """satellite: the multi-host schedule is exactly reproducible — two
    independent replays of the same (seed, topology) produce identical
    merged AND per-host event logs."""
    spec = LoadSpec(n_requests=5, vocab=128, rate=1.3, seed=7)
    logs = []
    for _ in range(2):
        sched, stats = simulate_sharded_schedule(
            sharded_workload(spec, 4), slots_per_host=2, gossip_delay=1)
        logs.append((sched.admissions, sched.releases,
                     [(h.admissions, h.releases) for h in sched.hosts],
                     stats))
    assert logs[0] == logs[1]


def test_gossip_delay_defers_visibility():
    """A request arriving at t is admitted no earlier than t + delay, and
    a freed slot is reused no earlier than release + delay."""
    for delay in (0, 1, 3):
        spec = LoadSpec(n_requests=4, vocab=64, rate=2.0, seed=5)
        wl = sharded_workload(spec, 2)
        arrival = {r.rid: r.arrival_step for reqs in wl for r in reqs}
        sched, _ = simulate_sharded_schedule(wl, slots_per_host=1,
                                             gossip_delay=delay)
        assert len(sched.admissions) == 8
        for step, gslot, rid, _ in sched.admissions:
            assert step >= arrival[rid] + delay
        # slot reuse respects the gossip horizon
        last_release = {}
        for step, gslot, rid, seq in sorted(
                sched.admissions + sched.releases, key=lambda e: e[3]):
            is_release = (step, gslot, rid, seq) in sched.releases
            if is_release:
                last_release[gslot] = step
            elif gslot in last_release:
                assert step >= last_release[gslot] + delay


def test_merged_workload_orders_like_the_gossip_queue():
    spec = LoadSpec(n_requests=5, vocab=64, rate=1.0, seed=2)
    merged = merge_workloads(sharded_workload(spec, 3))
    keys = [(r.arrival_step, r.home, r.rid) for r in merged]
    assert keys == sorted(keys)
    assert len(merged) == 15


def test_transport_equivalence_deterministic_sweep():
    """sim transport == collective transport (loopback gather), log for
    log, over a deterministic grid of topologies, delays, capacities and
    compaction settings — the no-hypothesis version of the equivalence
    property (CI also runs the hypothesis sweep)."""
    for n_hosts, spp, delay, cap, thresh, seed in [
            (1, 1, 0, 1, None, 0), (2, 3, 1, 2, None, 1),
            (4, 2, 2, 8, None, 2), (3, 4, 1, 1, 0.0, 3),
            (2, 4, 0, 4, 0.25, 4), (8, 2, 3, 2, 0.0, 5)]:
        spec = LoadSpec(n_requests=4, vocab=64, rate=1.5, seed=seed)
        a, sa = simulate_sharded_schedule(
            sharded_workload(spec, n_hosts), spp, delay,
            compact_threshold=thresh)
        b, sb = simulate_sharded_schedule(
            sharded_workload(spec, n_hosts), spp, delay,
            transport=CollectiveTransport(n_hosts, delay, capacity=cap),
            compact_threshold=thresh)
        key = (n_hosts, spp, delay, cap, thresh)
        assert a.admissions == b.admissions, key
        assert a.releases == b.releases, key
        assert a.compactions == b.compactions, key
        assert sa == sb, key
        for ha, hb in zip(a.hosts, b.hosts):
            assert (ha.admissions, ha.releases, ha.compactions) == \
                (hb.admissions, hb.releases, hb.compactions), key


def test_compaction_is_schedule_invariant_and_sound():
    """Deterministic compaction contract: the remap changes slot ids,
    never admission/release steps or rids; perms never cross a host
    boundary; the log replays soundly through COMPACT events; every
    request still completes."""
    spec = LoadSpec(n_requests=6, vocab=128, rate=2.0,
                    prompt_lens=(4, 8), gen_lens=(2, 5, 11), seed=3)
    for n_hosts, spp in [(2, 4), (4, 2), (1, 6)]:
        s0, st0 = simulate_sharded_schedule(
            sharded_workload(spec, n_hosts), spp, 1)
        s1, st1 = simulate_sharded_schedule(
            sharded_workload(spec, n_hosts), spp, 1,
            compact_threshold=0.0)
        assert len(s1.compactions) > 0, "threshold 0.0 never compacted"
        # admissions keep the slot-independent ready order exactly;
        # intra-step release order follows slot order, which the remap
        # permutes — compare releases as per-step multisets
        key = lambda evs: [(e[0], e[2]) for e in evs]
        assert key(s0.admissions) == key(s1.admissions)
        assert sorted(key(s0.releases)) == sorted(key(s1.releases))
        assert (st0.decode_steps, st0.idle_steps, st0.tokens_out) == \
            (st1.decode_steps, st1.idle_steps, st1.tokens_out)
        for step, perm, seq in s1.compactions:
            assert sorted(perm) == list(range(n_hosts * spp))
            assert all(new // spp == old // spp
                       for new, old in enumerate(perm))
        final = replay_slot_log(s1.admissions, s1.releases,
                                s1.compactions, n_hosts * spp)
        assert all(o is None for o in final)


def test_chaos_drill_recovers_from_mid_traffic_host_kill(report):
    """ISSUE 6 acceptance on the REAL engine (8-device subprocess): a
    committed FailPlan kills 1 of 4 hosts mid-traffic; the drill's own
    in-process asserts already proved FIFO re-admission, log equality
    with the model-free sim and slot-log soundness — this test pins the
    headline numbers into the pytest report too."""
    chaos = report["chaos"]
    assert chaos["verified"] is True
    first, last = chaos["arrival_span"]
    assert first < chaos["kill_step"] <= last     # genuinely mid-traffic
    base_tokens = chaos["base"]["tokens"]
    for tname in ("sim", "collective"):
        kr = chaos["kill_runs"][tname]
        assert kr["done"] and all(kr["done"].values())
        assert kr["stats"]["host_downs"] == 1
        assert kr["stats"]["requeued"] >= 1       # non-vacuous drill
        assert kr["stats"]["rejects"] == 0
        assert kr["tokens"] == base_tokens        # bit-identical recovery
        assert len(kr["log"]["reclaims"]) == kr["stats"]["requeued"]
    # engine log == model-free sim log under the kill, both transports
    assert chaos["kill_runs"]["sim"]["log"] == chaos["kill_sim"]["log"]
    assert (chaos["kill_runs"]["collective"]["log"]
            == chaos["kill_sim"]["log"])
    # host death never creates a new decode executable
    assert chaos["decode_compiles"] == 1


def test_kill_recovery_deterministic_twins():
    """No-hypothesis twins of the chaos property (CI also runs the
    hypothesis sweep): across fixed (topology, gossip delay, kill
    schedule) cases — single kill, double kill, kill + arrival-gossip
    slowdown — no request is lost, recovered tokens equal the fault-free
    twin's bit-for-bit, the slot log replays soundly through RECLAIMs,
    and the collective transport replays the identical recovery."""
    cases = [(2, 1, 0, "kill_host:0@2"),
             (4, 2, 1, "kill_host:1@3"),
             (3, 2, 2, "kill_host:2@4,kill_host:0@8"),
             (4, 1, 1, "kill_host:3@2,delay_arrivals:2@3")]
    for n_hosts, spp, gd, spec_str in cases:
        plan = FailPlan.parse(spec_str)
        spec = LoadSpec(n_requests=3, vocab=64, rate=1.5,
                        gen_lens=(2, 4, 7), seed=9)
        base_wl = sharded_workload(spec, n_hosts)
        simulate_sharded_schedule(base_wl, spp, gd)
        base_tokens = {r.rid: r.tokens for reqs in base_wl for r in reqs}

        kill_wl = sharded_workload(spec, n_hosts)
        sk, stk = simulate_sharded_schedule(kill_wl, spp, gd,
                                            failpoints=plan)
        reqs = [r for rs in kill_wl for r in rs]
        assert all(r.done and not r.rejected for r in reqs), spec_str
        assert {r.rid: r.tokens for r in reqs} == base_tokens, spec_str
        assert stk.host_downs == len(plan.kill_steps()), spec_str
        assert stk.requeued == len(sk.reclaims) >= 1, spec_str
        replay_slot_log(sk.admissions, sk.releases, sk.compactions,
                        sk.n_slots, rejects=sk.rejects,
                        reclaims=sk.reclaims)

        sc, stc = simulate_sharded_schedule(
            sharded_workload(spec, n_hosts), spp, gd,
            transport=CollectiveTransport(n_hosts, gd, capacity=4),
            failpoints=plan)
        assert (sk.admissions, sk.releases, sk.reclaims, sk.rejects,
                sk.host_downs) == \
            (sc.admissions, sc.releases, sc.reclaims, sc.rejects,
             sc.host_downs), spec_str
        assert stk == stc, spec_str


def test_overload_drill_sheds_and_degrades_on_the_real_engine(report):
    """ISSUE 10 acceptance on the REAL engine (8-device subprocess): the
    committed surge+slow_decode FailPlan overloads a 4-host pool running
    the committed AdmissionPolicy; the drill's own in-process asserts
    already proved shed determinism, twin bit-identity, log equality and
    zero recompiles — this test pins the headline numbers into the
    pytest report too."""
    ov = report["overload"]
    assert ov["verified"] is True
    assert ov["overload_steps"], "plan injected no overload"
    assert ov["base"]["stats"]["sheds"] == 0
    assert all(ov["base"]["done"].values())
    base_tokens = ov["base"]["tokens"]
    for tname in ("sim", "collective"):
        sr = ov["surge_runs"][tname]
        shed = {str(rid) for rid in sr["shed_rids"]}   # JSON string keys
        assert sr["stats"]["sheds"] == len(shed) > 0, tname
        assert sr["stats"]["degrades"] >= 2, tname   # escalate + restore
        assert sr["stats"]["rejects"] == 0, tname
        # served tokens bit-identical to the unloaded twin; shed requests
        # got NO tokens
        for rid, d in sr["done"].items():
            if rid in shed:
                assert sr["tokens"][rid] == [], (tname, rid)
            else:
                assert d and sr["tokens"][rid] == base_tokens[rid], \
                    (tname, rid)
        assert sr["slo_attainment"] == slo_attainment(
            ov["n_requests"] - len(shed), ov["n_requests"])
    # shed decisions identical across transports and the model-free sim
    assert (ov["surge_runs"]["sim"]["shed_rids"]
            == ov["surge_runs"]["collective"]["shed_rids"]
            == ov["surge_sim"]["shed_rids"])
    assert ov["surge_runs"]["sim"]["log"] == ov["surge_sim"]["log"]
    assert (ov["surge_runs"]["collective"]["log"]
            == ov["surge_sim"]["log"])
    # zero recompiles through every DEGRADE/RESTORE transition
    assert all(n <= 1 for n in ov["stage_decode_compiles"].values())
    assert ov["stage_decode_compiles"]["0"] == 1


def test_overload_deterministic_twins():
    """No-hypothesis twins of the overload property (CI also runs the
    hypothesis sweep): across fixed (topology, surge, deadline, queue
    bound) cases — every request is exactly one of completed / shed,
    shed requests were never admitted, FIFO holds among survivors, and
    the collective transport sheds the identical set."""
    cases = [(2, 1, 0, "surge:3@0", 2, None),
             (4, 2, 1, "surge:2@1,slow_decode:3@2", 4, 2),
             (3, 1, 1, "slow_decode:4@0", 3, 1),
             (2, 2, 0, "surge:4@2", 1, None)]
    policy_kw = dict(pressure_window=2, degrade_lo=0.25, degrade_hi=0.5,
                     restore_below=0.1)
    any_shed = False
    for n_hosts, spp, gd, spec_str, slack, depth in cases:
        key = (n_hosts, spp, gd, spec_str)
        plan = FailPlan.parse(spec_str)
        policy = AdmissionPolicy(max_queue_depth=depth, **policy_kw)
        spec = LoadSpec(n_requests=4, vocab=64, rate=2.0,
                        gen_lens=(2, 4, 7), seed=13)
        wl = overload_workload(spec, n_hosts, surge_start=0,
                               surge_factor=2, deadline_slack=slack)
        sk, stk = simulate_sharded_schedule(wl, spp, gd, failpoints=plan,
                                            admission_policy=policy)
        reqs = [r for rs in wl for r in rs]
        assert all(r.done for r in reqs), key
        shed = {r.rid for r in reqs if r.shed}
        any_shed |= bool(shed)
        assert stk.sheds == len(shed) == len(sk.sheds), key
        for r in reqs:
            if r.shed:
                assert r.admitted_step < 0 and not r.tokens, key
            else:
                assert r.admitted_step >= 0, key
                assert len(r.tokens) == r.max_gen, key
        # FIFO among survivors on the replicated queue key
        eff = {r.rid: (plan.effective_arrival(r.arrival_step), r.home,
                       r.rid) for r in reqs}
        order = [rid for _, _, rid, seq in
                 sorted(sk.admissions, key=lambda e: e[3])]
        assert [eff[rid] for rid in order] == \
            sorted(eff[rid] for rid in order), key
        replay_slot_log(sk.admissions, sk.releases, sk.compactions,
                        sk.n_slots, rejects=sk.rejects,
                        reclaims=sk.reclaims)

        sc, stc = simulate_sharded_schedule(
            overload_workload(spec, n_hosts, surge_start=0,
                              surge_factor=2, deadline_slack=slack),
            spp, gd,
            transport=CollectiveTransport(n_hosts, gd, capacity=16),
            failpoints=plan, admission_policy=policy)
        assert sk.sheds == sc.sheds, key
        assert sk.degrades == sc.degrades, key
        assert (sk.admissions, sk.releases) == \
            (sc.admissions, sc.releases), key
        assert stk == stc, key
    assert any_shed, "no case shed anything — the twins are vacuous"


def test_sim_prefill_reject_at_cap_and_retry_below_cap():
    """fail_prefill below PREFILL_MAX_ATTEMPTS is invisible to the
    schedule (the pool retries another worker); AT the cap the victim is
    REJECTed — slot freed, logged, everyone else token-identical."""
    from repro.serving import PREFILL_MAX_ATTEMPTS

    spec = LoadSpec(n_requests=3, vocab=64, rate=1.0,
                    gen_lens=(2, 4), seed=4)
    base_wl = sharded_workload(spec, 2)
    simulate_sharded_schedule(base_wl, 2, 1)
    base_tokens = {r.rid: r.tokens for reqs in base_wl for r in reqs}
    victim = sorted(base_tokens)[1]

    # below the cap: nothing observable in the model-free schedule
    wl = sharded_workload(spec, 2)
    s_ok, st_ok = simulate_sharded_schedule(
        wl, 2, 1, failpoints=FailPlan.parse(
            f"fail_prefill:{victim}:{PREFILL_MAX_ATTEMPTS - 1}"))
    assert st_ok.rejects == 0 and not s_ok.rejects
    assert {r.rid: r.tokens for rs in wl for r in rs} == base_tokens

    # at the cap: REJECT — victim unserved, others complete untouched
    wl = sharded_workload(spec, 2)
    s_rj, st_rj = simulate_sharded_schedule(
        wl, 2, 1, failpoints=FailPlan.parse(
            f"fail_prefill:{victim}:{PREFILL_MAX_ATTEMPTS}"))
    assert st_rj.rejects == 1
    assert [rid for _, _, rid, _ in s_rj.rejects] == [victim]
    for r in (r for rs in wl for r in rs):
        if r.rid == victim:
            assert r.rejected and r.tokens == [] and r.done
        else:
            assert not r.rejected and r.tokens == base_tokens[r.rid]
    replay_slot_log(s_rj.admissions, s_rj.releases, s_rj.compactions,
                    s_rj.n_slots, rejects=s_rj.rejects,
                    reclaims=s_rj.reclaims)


def test_corrupted_replica_raises_within_one_round():
    """Digest satellite: a replica whose reported state digest diverges
    crashes the exchange round it reports in — BOTH transports, and the
    raise names the disagreeing host and the step."""
    spec = LoadSpec(n_requests=3, vocab=64, rate=1.0, seed=2)
    plan = FailPlan.parse("corrupt_digest:1@2")
    for transport in (None,
                      CollectiveTransport(3, 1, capacity=4)):
        with pytest.raises(ReplicaDivergence, match=r"step 2.*\[1\]"):
            simulate_sharded_schedule(sharded_workload(spec, 3), 2, 1,
                                      transport=transport,
                                      failpoints=plan)


def test_hung_round_past_deadline_raises_timeout():
    """Deadline satellite: an injected hang longer than the per-round
    deadline raises TransportTimeout instead of stalling the pool."""
    spec = LoadSpec(n_requests=3, vocab=64, rate=1.0, seed=2)
    plan = FailPlan.parse("hang_round:99@2")
    for transport in (None,
                      CollectiveTransport(3, 1, capacity=4)):
        with pytest.raises(TransportTimeout, match="step 2"):
            simulate_sharded_schedule(sharded_workload(spec, 3), 2, 1,
                                      transport=transport,
                                      failpoints=plan)
    # a hang UNDER the deadline is survivable and schedule-invariant
    base_wl = sharded_workload(spec, 3)
    s0, _ = simulate_sharded_schedule(base_wl, 2, 1)
    wl = sharded_workload(spec, 3)
    s1, _ = simulate_sharded_schedule(
        wl, 2, 1, failpoints=FailPlan.parse("hang_round:4@2"))
    assert (s0.admissions, s0.releases) == (s1.admissions, s1.releases)


def test_delay0_same_step_release_readmits_instead_of_dropping():
    """Regression: with gossip_delay=0 a slot freed during the admit
    phase (max_gen=1) is visible the same step; the driver must re-admit
    the waiting request at the same clock tick, not break the loop and
    drop it (the pre-refactor next_event_time filtered the candidate
    out)."""
    reqs = [Request(rid=i, prompt=np.zeros(2, np.int32), max_gen=1,
                    arrival_step=0, home=0) for i in range(3)]
    sched, stats = simulate_sharded_schedule([reqs], slots_per_host=1,
                                             gossip_delay=0)
    assert all(r.done for r in reqs)
    assert len(sched.admissions) == 3
    # all three turned around at step 0: pure same-tick re-admission
    assert [e[0] for e in sched.admissions] == [0, 0, 0]
