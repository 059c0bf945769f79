"""Web-scale Bloom retrieval serving (DESIGN.md §11).

The paper is a recommender-systems paper; this module is the serving
scenario that makes its "millions of users" claim concrete: top-k item
retrieval over a Bloom-compressed catalog of d >= 10M items, served
through the SAME slot-pool machinery as the LM engine — Scheduler /
RequestQueue / ServeStats / PrefillPool are reused verbatim, only the
per-slot program differs (engine.SlotProgram):

  * prefill (``RetrievalProgram``): the request's padded item-id set is
    Bloom-encoded (core.bloom.encode, Eq. 1) and pushed through a small
    FF tower (models/recommender.py) to an m-dim logits row — that row,
    with the slot it goes to, IS the slot payload (no KV cache, no
    first token);
  * decode (``steps.make_retrieval_decode_step``): ONE occupancy-aware
    streaming Eq. 3 top-k over the whole catalog
    (io.recover_topk_spec), after which every served slot retires —
    the ``oneshot`` request kind: prefill -> single recover step ->
    retire, no autoregressive loop.

Never materialized: the (n_slots, d) score matrix and the (d, m) dense
item table.  At d=10M, m=8192 the dense table alone is 320 GB — the
catalog regime where only the streaming path serves at all; the
modeled-bytes gap vs that dense-table oracle is what
benchmarks/bench_serving.py commits and CI gates (retrieval.* rows).

Everything is deterministic: the Zipf workload is a pure function of
(seed, host) (loadgen.retrieval_workload), the schedule is a pure
function of (workload, n_slots), and the decode tie-break contract
(lowest item id wins on equal Eq. 3 scores) pins the recovered ids
bit-identically across replays and decode impls — asserted by the CLI
below and by tests/test_retrieval.py.

``python -m repro.serving.retrieval`` runs the acceptance drill: a
seeded Zipf run at d >= 10M through the slot pool, twice, hard-asserting
bit-identical top-k ids, a sound slot log, and tie-aware untrained
MAP/RR << 1 at eval scale, then prints the ``retrieval: verified``
marker the CI job greps for.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.retrieval import RetrievalConfig, get_retrieval_config
from repro.core import bloom as bloom_lib
from repro.core import quant
from repro.kernels.bloom_decode_topk import modeled_hbm_bytes
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import recommender as rec_lib
from repro.serving import admission as admission_lib
from repro.serving import engine as engine_lib
from repro.serving.admission import AdmissionPolicy
from repro.serving.engine import PrefillPool, SlotProgram, run_slot_loop
from repro.serving.failpoints import FailPlan
from repro.serving.loadgen import (RetrievalLoadSpec, assert_fresh_instances,
                                   retrieval_workload)
from repro.serving.scheduler import Request, ServeStats
from repro.train import metrics as metrics_lib

# full-score eval materializes (B, d) — fine for the smoke/web1m specs,
# a 40 GB allocation at web10m; the serving path never does this
EVAL_MAX_CATALOG = 2_000_000


def init_retrieval_params(rcfg: RetrievalConfig, key=None):
    """FF tower params: m-dim Bloom code in, m-dim logits out."""
    if key is None:
        key = jax.random.PRNGKey(rcfg.seed)
    return rec_lib.ff_init(key, rcfg.m, rcfg.hidden, rcfg.m)


@dataclasses.dataclass
class _RetrievalState:
    """Retrieval slot-pool state: the device-resident (n_slots, m)
    logits pool, a host mirror of the occupancy mask (the decode step's
    ``active`` input AND the bytes model's occupancy argument), and the
    run's accumulated modeled streaming bytes."""
    pool: object
    live: np.ndarray
    streaming_bytes: int = 0


class RetrievalProgram(SlotProgram):
    """The one-shot retrieval slot program (see module doc).

    Prefill packs the request's -1-padded items and its admitted slot
    into one (1, c_max + 1) int32 host array, the slot in the last
    column, and uploads it once; one jitted call runs the tower and
    returns the (m,) logits row and the slot as a device int32 scalar.
    It emits ``((row, slot), None)`` — there is no first token, the
    slot's whole output comes from the single recover step.  ``insert``
    hands both device values to the jitted pool write, so a query
    dispatches two device programs and no eager op between them.  The
    decode half (constructed with ``n_slots``) owns the (n_slots, m)
    logits pool and the one occupancy-aware streaming Eq. 3 top-k step
    over the catalog, after which every served slot retires
    (``oneshot``)."""

    kind = "oneshot"
    oneshot = True
    engine_label = "the retrieval engine"

    def __init__(self, rcfg: RetrievalConfig,
                 n_slots: Optional[int] = None,
                 admission_policy=None):
        self.rcfg = rcfg
        self.n_slots = n_slots
        tower = steps_lib.make_retrieval_prefill_step(rcfg)

        def prefill_row(params, packed):
            """(1, c_max + 1) items and slot -> (m,) row, int32 slot."""
            return tower(params, packed[:, :-1])[0], packed[0, -1]

        self._prefill = jax.jit(prefill_row)
        if n_slots is None:
            return                      # prefill-only program
        self._decode = jax.jit(steps_lib.make_retrieval_decode_step(rcfg))
        self._insert = jax.jit(
            lambda pool, row, slot: pool.at[slot].set(row),
            donate_argnums=(0,))
        # degrade ladder (DESIGN.md §14): "stage 2 shrinks retrieval
        # top-k" — each stage's narrower streaming decode is pre-built;
        # under the pinned lowest-id tie-break a degraded request's ids
        # are a bit-identical PREFIX of the full-width result
        self._stage = admission_lib.STAGE_NORMAL
        self._stage_topk = {
            st: admission_lib.stage_topk(rcfg.topk, st, admission_policy)
            for st in range(1, admission_policy.max_stage + 1)
        } if admission_policy is not None else {}
        self._stage_topk[admission_lib.STAGE_NORMAL] = rcfg.topk
        self._stage_decodes = engine_lib.build_stage_decodes(
            self._decode, rcfg.topk, admission_policy,
            lambda k: jax.jit(steps_lib.make_retrieval_decode_step(
                dataclasses.replace(rcfg, topk=k))))

    # -- prefill half --------------------------------------------------
    def prefill(self, params, req: Request, device=None):
        packed = np.full((1, self.rcfg.c_max + 1), -1, np.int32)
        packed[0, :req.prompt_len] = req.prompt
        packed[0, -1] = req.slot
        with tracing.span("h2d", what="items", bytes=packed.nbytes):
            x = jax.device_put(packed, device)
        with tracing.span("launch", fn="prefill", rid=req.rid):
            return self._prefill(params, x), None

    # -- decode half ---------------------------------------------------
    def check_admit(self, req: Request) -> None:
        assert req.prompt_len <= self.rcfg.c_max, (
            f"request {req.rid}: {req.prompt_len} input items exceeds "
            f"c_max {self.rcfg.c_max}")

    def init_state(self, n_slots: int) -> _RetrievalState:
        assert n_slots == self.n_slots
        return _RetrievalState(
            pool=jnp.zeros((n_slots, self.rcfg.m), jnp.float32),
            live=np.zeros((n_slots,), bool))

    def reset_slots(self, state: _RetrievalState) -> None:
        state.live[:] = False

    def insert(self, state: _RetrievalState, req: Request, payload,
               stats: ServeStats) -> bool:
        (row, slot), first = payload
        assert first is None, "oneshot prefill emits no token"
        with tracing.span("launch", fn="insert", rid=req.rid):
            state.pool = self._insert(state.pool, row, slot)
        state.live[req.slot] = True
        return True

    def set_stage(self, stage: int) -> None:
        if stage not in self._stage_decodes:
            raise RuntimeError(
                f"{self.engine_label}: degrade stage {stage} was not "
                "pre-built — construct the program with the run's "
                "admission_policy (DESIGN.md §14)")
        self._stage = stage

    def step(self, params, state: _RetrievalState):
        live = int(state.live.sum())
        with tracing.span("h2d", what="live", bytes=state.live.nbytes):
            active = jnp.asarray(state.live)
        with tracing.span("launch", fn="decode", live=live):
            scores, ids = self._stage_decodes[self._stage](state.pool,
                                                           active)
        # bytes model follows the table_dtype knob (DESIGN.md §13): a
        # quantized decode stores the logp rows narrow, rehashes
        # in-kernel (no (d, k) stream) and — int8 only — reads one f32
        # scale per live row; "auto" keeps the legacy exact model.
        # The top-k term follows the degrade stage's served width.
        td = self.rcfg.table_dtype
        td = None if td == "auto" else td
        state.streaming_bytes += modeled_hbm_bytes(
            state.live, self.rcfg.b_tile, m=self.rcfg.m, d=self.rcfg.d,
            k=self.rcfg.k, topk=self._stage_topk[self._stage],
            logp_itemsize=quant.table_itemsize(td),
            inkernel_hash=td is not None,
            row_scales=td == "int8")
        # the device's wait, so that the copies below time the copy only
        with tracing.span("wait", live=live):
            jax.block_until_ready((scores, ids))
        with tracing.span("d2h", what="ids", bytes=ids.nbytes):
            ids_np = np.asarray(ids)
        with tracing.span("d2h", what="scores", bytes=scores.nbytes):
            scores_np = np.asarray(scores)
        return ids_np, scores_np

    def emit(self, state: _RetrievalState, req: Request, slot: int, out,
             stats: ServeStats) -> bool:
        # one-shot: every slot that decoded retires with its top-k
        ids_np, scores_np = out
        req.topk_ids = [int(i) for i in ids_np[slot]]
        req.topk_scores = [float(s) for s in scores_np[slot]]
        req.tokens.append(int(ids_np[slot, 0]))
        stats.tokens_out += 1
        state.live[slot] = False
        return True


class RetrievalEngine:
    """Continuous-batching engine for ``oneshot`` retrieval requests.

    Admission, rejection, event logging and stats are the LM engine's
    (Scheduler / PrefillPool); the slot pool is a device-resident
    (n_slots, m) logits buffer + active mask instead of a KV-cache tree,
    and every live slot retires right after the step that recovers its
    top-k — so the schedule batches same-step admissions through one
    streaming decode over the catalog.

    After ``run`` the modeled decode bytes of the run are on
    ``self.modeled_bytes``: per-step streaming bytes from the kernel
    bytes model evaluated at the step's actual occupancy mask (the
    single source, kernels/bloom_decode_topk.modeled_hbm_bytes) and the
    dense-table oracle twin — all deterministic integers.
    """

    def __init__(self, rcfg: RetrievalConfig, params, *, n_slots: int,
                 prefill_workers: int = 1,
                 failpoints: Optional[FailPlan] = None,
                 admission_policy: Optional[AdmissionPolicy] = None):
        assert n_slots >= 1
        self.rcfg = rcfg
        self.params = params
        self.n_slots = n_slots
        self.failpoints = failpoints if failpoints else None
        self.policy = admission_policy
        self.program = RetrievalProgram(rcfg, n_slots=n_slots,
                                        admission_policy=admission_policy)
        self.prefill_pool = PrefillPool(
            None, params, topk=rcfg.topk, n_workers=prefill_workers,
            failpoints=self.failpoints, program=self.program)
        self.modeled_bytes: Dict[str, int] = {}

    def _dense_oracle_step_bytes(self) -> int:
        """HBM bytes of ONE dense-table decode step over the full pool:
        read the (d, m) f32 item table and the (B, m) logp rows, write
        AND re-read the (B, d) f32 score matrix (materialize, then
        top-k), flush the (B, topk) f32+i32 outputs.  The oracle the
        streaming path is gated against — at web10m the table term alone
        is 320 GB/step."""
        r, B = self.rcfg, self.n_slots
        return (r.d * r.m * 4 + B * r.m * 4 + 2 * B * r.d * 4
                + B * r.topk * 8)

    def run(self, requests: List[Request]
            ) -> Tuple[Dict[int, Request], ServeStats]:
        """Serve ``oneshot`` requests through the generic slot loop
        (engine.run_slot_loop — the SAME function the LM engine runs);
        mutates and returns them with ``topk_ids`` / ``topk_scores``
        filled (and ``tokens`` holding the top-1 item, so shared
        latency/throughput accounting works unchanged)."""
        results, stats, sched, state = run_slot_loop(
            self.program, self.params, self.prefill_pool, requests,
            self.n_slots, failpoints=self.failpoints,
            admission_policy=self.policy)
        self._sched = sched          # exposed for the simulation tests
        self.modeled_bytes = {
            "streaming_bytes": int(state.streaming_bytes),
            "dense_oracle_bytes": int(self._dense_oracle_step_bytes()
                                      * stats.decode_steps),
            "dense_oracle_step_bytes": self._dense_oracle_step_bytes(),
        }
        return results, stats


def evaluate_retrieval(rcfg: RetrievalConfig, params,
                       requests: List[Request],
                       table_dtype: Optional[str] = None
                       ) -> Dict[str, float]:
    """Offline ranking eval of served requests against their held-out
    targets, with the user's input items excluded from the ranking.

    Materializes the full (B, d) Eq. 3 score matrix (core.bloom.
    decode_scores — chunked, but still (B, d) at the end), so it is
    capped at eval-scale catalogs; the SERVING path never does this.
    Metrics are the tie-aware train/metrics.py: mid-rank RR and
    stable-sort MAP, so an untrained tower scores << 1 instead of the
    optimistic-tie 1.0 the old rank computation produced.

    ``table_dtype`` (DESIGN.md §13) fake-quantizes the (B, m) pool
    logits per row before Eq. 3 — the exact values a quantized Pallas
    decode ranks through — so the metrics measure what a quantized
    store would actually serve (the sweep's int8 dual-eval retention).
    """
    assert rcfg.d <= EVAL_MAX_CATALOG, (
        f"full-score eval at d={rcfg.d} would materialize a "
        f"(B, {rcfg.d}) matrix; eval on the smoke/web1m specs")
    served = [r for r in requests
              if r.done and not r.rejected and not r.shed
              and r.targets is not None and len(r.targets)]
    if not served:
        return {"map": 0.0, "rr": 0.0, "accuracy": 0.0, "n_evaluated": 0}
    B = len(served)
    prompts = np.full((B, rcfg.c_max), -1, np.int32)
    n_t = max(len(r.targets) for r in served)
    targets = np.full((B, n_t), -1, np.int32)
    for i, r in enumerate(served):
        prompts[i, :r.prompt_len] = np.asarray(r.prompt, np.int32)
        targets[i, :len(r.targets)] = np.asarray(r.targets, np.int32)
    logits = jax.jit(steps_lib.make_retrieval_prefill_step(rcfg))(
        params, jnp.asarray(prompts))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    td = quant.resolve_table_dtype(table_dtype)
    if td is not None:
        q, s = quant.quantize_table(logp, td)
        logp = quant.dequantize_table(q, s)
    scores = np.asarray(bloom_lib.decode_scores(rcfg.spec(), logp,
                                                chunk=rcfg.chunk))
    # RR / accuracy score the FIRST held-out target (the single-correct-
    # item measures of Sec. 4.1); MAP scores the full held-out set
    return {
        "map": metrics_lib.mean_average_precision(scores, targets,
                                                  excludes=prompts),
        "rr": metrics_lib.reciprocal_rank(scores, targets[:, 0],
                                          exclude=prompts),
        "accuracy": metrics_lib.accuracy(scores, targets[:, 0],
                                         exclude=prompts),
        "n_evaluated": B,
    }


# ---------------------------------------------------------------------------
# CLI acceptance drill (the CI retrieval job greps "retrieval: verified")
# ---------------------------------------------------------------------------

def _drill(rcfg: RetrievalConfig, n_requests: int, n_slots: int,
           seed: int) -> Dict[str, object]:
    """Run the seeded Zipf workload through the slot pool TWICE from
    fresh request copies and hard-assert the acceptance criteria."""
    load = RetrievalLoadSpec(n_requests=n_requests, catalog=rcfg.d,
                             c_max=rcfg.c_max, rate=2.0, seed=seed)
    wl = retrieval_workload(load)
    params = init_retrieval_params(rcfg)
    engine = RetrievalEngine(rcfg, params, n_slots=n_slots)

    wl_a = [r.fresh_copy() for r in wl]
    wl_b = [r.fresh_copy() for r in wl]
    assert_fresh_instances(wl_a, wl_b)
    res_a, st_a = engine.run(wl_a)
    res_b, st_b = engine.run(wl_b)

    assert all(r.done and not r.rejected for r in res_a.values())
    for rid, ra in res_a.items():
        rb = res_b[rid]
        assert len(ra.topk_ids) == rcfg.topk
        assert all(0 <= i < rcfg.d for i in ra.topk_ids)
        assert ra.topk_ids == rb.topk_ids, (
            f"rid {rid}: top-k ids drifted across replays — the decode "
            "path is not deterministic")
        assert ra.topk_scores == rb.topk_scores
    assert st_a.decode_steps == st_b.decode_steps
    from repro.serving.control import replay_slot_log
    replay_slot_log(engine._sched.admissions, engine._sched.releases,
                    [], n_slots, rejects=engine._sched.rejects)
    mb = engine.modeled_bytes
    return {
        "config": rcfg.name, "d": rcfg.d, "m": rcfg.m, "k": rcfg.k,
        "impl": rcfg.resolved_impl, "n_requests": n_requests,
        "n_slots": n_slots, "decode_steps": st_a.decode_steps,
        "utilization": round(st_a.utilization, 4),
        "streaming_bytes": mb["streaming_bytes"],
        "dense_oracle_bytes": mb["dense_oracle_bytes"],
        "bytes_ratio": round(mb["dense_oracle_bytes"]
                             / max(mb["streaming_bytes"], 1), 1),
        "wall_s": round(st_a.wall_s, 3),
    }


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="web10m",
                    help="retrieval config preset (default: web10m — the "
                         "d >= 10M acceptance scale)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default=None,
                    help="override the decode impl (auto|xla|pallas)")
    ap.add_argument("--out", default=None, help="write the report JSON")
    args = ap.parse_args()

    over = {"impl": args.impl} if args.impl else {}
    rcfg = get_retrieval_config(args.config, **over)
    report = _drill(rcfg, args.requests, args.slots, args.seed)

    # untrained-model ranking sanity at eval scale: with the tie-aware
    # metrics a random tower must score << 1 (the old optimistic-tie RR
    # reported ~1.0 on ties regardless of model quality)
    smoke = get_retrieval_config("smoke")
    load = RetrievalLoadSpec(n_requests=8, catalog=smoke.d,
                             c_max=smoke.c_max, rate=2.0, seed=args.seed)
    sparams = init_retrieval_params(smoke)
    sengine = RetrievalEngine(smoke, sparams, n_slots=4)
    sres, _ = sengine.run([r.fresh_copy() for r in retrieval_workload(load)])
    ev = evaluate_retrieval(smoke, sparams, list(sres.values()))
    assert ev["n_evaluated"] > 0
    assert ev["rr"] < 0.1 and ev["map"] < 0.1, (
        f"untrained tower ranks suspiciously well (rr={ev['rr']:.4f}, "
        f"map={ev['map']:.4f}) — tie handling regressed?")
    report["eval_smoke"] = {k: round(v, 6) if isinstance(v, float) else v
                           for k, v in ev.items()}
    report["verified"] = True

    print(json.dumps(report, indent=1, sort_keys=True))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(f"retrieval: verified ({rcfg.name}: d={rcfg.d}, "
          f"{report['decode_steps']} decode steps, bytes ratio "
          f"{report['bytes_ratio']}x vs dense oracle)")


if __name__ == "__main__":
    main()
