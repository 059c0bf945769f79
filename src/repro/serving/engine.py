"""Slot-based continuous-batching serving engine.

The PR-1 kernel work made one decode step cheap (fused Bloom decode-topk,
no (B, d) score matrix in HBM); this module makes a *system* out of it:

  * a preallocated per-slot cache pool (``init_lm_cache`` at ``n_slots`` x
    ``max_len``), with prefill caches written into a freed slot via
    ``steps.insert_cache_slot`` (lax.dynamic_update_slice — the
    generalization of the old serve.py ``pad_caches_to``);
  * ONE jitted decode step for the whole pool: a per-slot position vector
    lets every slot sit at its own sequence offset, so admitting a request
    mid-flight never recompiles (models/attention.decode_self_attention
    handles scalar and (B,) pos);
  * host-side admission/retirement per step (serving/scheduler.py): freed
    slots are refilled from the queue every decode step, per-slot stop
    conditions (max_gen / EOS id) retire them;
  * device-resident slot state: (tokens, pos, active) stay on device for
    the whole run and advance from the decode step's own outputs; the
    host writes them only on admit/retire events instead of re-uploading
    all three every decode step (the one d2h transfer left in the
    steady-state loop is the new-token download the scheduler needs);
  * per-row math is *bit-identical* to the static path — a request served
    through the pool produces exactly the tokens it produces alone
    (asserted by tests/test_serving.py), because every decode op is
    row-independent and the masked slot cache write stores the same values
    as the static dynamic-slice write.

``Engine.run_static`` is the A/B baseline: classic static batching over
the same jitted steps — groups of n_slots start together and drain until
the longest request finishes, burning slot-steps on retired slots.  The
decode-step/slot-utilization gap between the two is what
benchmarks/bench_serving.py commits to BENCH_serving.json.

Time is counted in decode steps (deterministic on CPU CI); wall-clock is
recorded but never asserted on.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.configs.base import ModelConfig
from repro.launch import sharding as sharding_lib
from repro.launch import steps as steps_lib
from repro.models import io as io_lib
from repro.models import transformer as tf
from repro.serving import admission as admission_lib
from repro.serving.admission import AdmissionPolicy
from repro.serving.failpoints import FailPlan, PREFILL_MAX_ATTEMPTS
from repro.serving.scheduler import (Request, RequestQueue, Scheduler,
                                     ServeStats)


class PrefillFault(RuntimeError):
    """Injected prefill failure (FailPlan ``fail_prefill``) — raised at
    the same point a real worker crash would surface."""


def assert_request_fits(req: Request, max_len: int) -> None:
    """The one pool-capacity precondition, shared by every admission path
    (continuous, static, sharded)."""
    assert req.prompt_len + req.max_gen <= max_len, (
        f"request {req.rid}: prompt {req.prompt_len} + max_gen "
        f"{req.max_gen} exceeds pool max_len {max_len}")


def assert_kind(requests, kind: str, engine: str) -> None:
    """Engines serve exactly one request kind; a mixed workload is a
    routing bug upstream, not something to half-serve."""
    for r in requests:
        if r.kind != kind:
            raise NotImplementedError(
                f"request {r.rid}: kind={r.kind!r} — {engine} serves "
                f"kind={kind!r} only; oneshot retrieval requests go "
                "through serving/retrieval.RetrievalEngine and LM "
                "requests through serving/engine.Engine (DESIGN.md §11)")


class SlotProgram:
    """Arch-agnostic per-slot program: WHAT one slot computes, decoupled
    from WHEN the engine/scheduler runs it (the ROADMAP "continuous
    batching for every architecture" refactor; DESIGN.md §11–12).

    The protocol has two halves:

      * **prefill half** — ``prefill`` turns a request into the payload
        its slot will hold: (caches, first_token) for the autoregressive
        LM program below, a (m,) logits row (and no first token) for the
        one-shot retrieval program in serving/retrieval.py.  This is the
        half ``PrefillWorker``/``PrefillPool`` run, possibly on their own
        mesh slice — a prefill-only program never builds decode state.
      * **decode half** — the program OWNS its slot-pool state and the
        jitted callables that advance it.  ``init_state`` allocates the
        device-resident pool; ``insert`` consumes a prefill payload into
        a slot (returning whether the slot went live); ``step`` runs ONE
        jitted decode over the whole pool and returns host-side outputs;
        ``emit`` writes one slot's outputs into its request (returning
        whether the slot retires).  ``run_slot_loop`` below drives any
        program through the Scheduler/RequestQueue machinery — the LM
        engine and the retrieval engine are the same loop with a
        different program plugged in.

    ``kind`` names the Request.kind the program serves; ``oneshot``
    programs take exactly one recover step after prefill and retire.
    """

    kind = "lm"
    oneshot = False
    engine_label = "a slot-program engine"

    # -- prefill half --------------------------------------------------
    def prefill(self, params, req: Request, device=None):
        raise NotImplementedError

    # -- decode half ---------------------------------------------------
    def check_admit(self, req: Request) -> None:
        """Per-request capacity precondition, asserted at admission."""
        raise NotImplementedError

    def init_state(self, n_slots: int):
        """Allocate the program's device-resident slot-pool state."""
        raise NotImplementedError

    def reset_slots(self, state) -> None:
        """Reset per-slot occupancy for a fresh static group (persistent
        pool buffers survive; only the who-is-live state clears)."""
        raise NotImplementedError

    def insert(self, state, req: Request, payload, stats: ServeStats
               ) -> bool:
        """Consume ``payload`` (what ``prefill`` emitted) into
        ``req.slot``; record any prefill-time output on the request.
        Returns True if the slot is now live (needs decode steps),
        False if the request finished at prefill time."""
        raise NotImplementedError

    def step(self, params, state):
        """ONE jitted decode step over the whole pool; advances
        ``state`` in place and returns host-side outputs for ``emit``."""
        raise NotImplementedError

    def emit(self, state, req: Request, slot: int, out,
             stats: ServeStats) -> bool:
        """Write slot ``slot``'s share of ``out`` into ``req``.
        Returns True if the slot retires (the loop releases it)."""
        raise NotImplementedError

    def set_stage(self, stage: int) -> None:
        """Degrade-ladder hook (DESIGN.md §14): swap to ``stage``'s
        PRE-BUILT decode callable — a jit swap, never a compile.
        Programs built without an ``admission_policy`` serve stage 0
        only; asking them to degrade is a wiring bug, not a fallback."""
        if stage != admission_lib.STAGE_NORMAL:
            raise RuntimeError(
                f"{self.engine_label} was built without an "
                f"admission_policy — degrade stage {stage} has no "
                "pre-built decode callable (DESIGN.md §14: stage jits "
                "are constructed up front so a transition never "
                "compiles)")


def build_stage_decodes(stage0, topk: int,
                        policy: Optional[AdmissionPolicy], make):
    """stage -> PRE-BUILT jitted decode callable, shared by the LM,
    sharded and retrieval programs (DESIGN.md §14).

    ``stage0`` is the already-built full-width jit; ``make(k)`` builds
    (but does not compile — jax.jit is lazy) the width-``k`` variant.
    Stages whose ``admission.stage_topk`` width equals an already-built
    stage share its jit object, so cache-size accounting stays exact:
    every distinct executable in the ladder compiles at most once, and a
    DEGRADE/RESTORE transition is a dict lookup."""
    stages = {admission_lib.STAGE_NORMAL: stage0}
    if policy is None:
        return stages
    by_width = {topk: stage0}
    for st in range(1, policy.max_stage + 1):
        k = admission_lib.stage_topk(topk, st, policy)
        if k not in by_width:
            by_width[k] = make(k)
        stages[st] = by_width[k]
    return stages


@dataclasses.dataclass
class _LMState:
    """Device-resident LM slot-pool state: the KV-cache pool plus the
    (tokens, pos, active) slot vectors that stay on device for the whole
    run (host writes only on admit/retire events — see module doc).
    ``live`` is the host's copy of ``active``, kept on the same events,
    so a step's spans can say how many rows it decodes."""
    caches: object
    tokens: object
    pos: object
    active: object
    live: np.ndarray


class LMSlotProgram(SlotProgram):
    """The autoregressive token-LM program: jitted prefill + first-token
    Eq. 3 recovery, and (when constructed with ``max_len``) the decode
    half — slot KV-cache pool, one jitted pool-decode step, device-side
    (tokens, pos, active) advance.  Prefill is always B=1 at the exact
    prompt length — bit-identical to serving the request alone.

    A prefill-only instance (``PrefillWorker``'s default; the sharded
    engine's disaggregated prefill slice) omits ``max_len`` and never
    builds the decode-side jits or the pool template.  Under a ``dist``
    it is also the sharded engine's data plane (DESIGN.md §8)."""

    kind = "lm"
    oneshot = False
    engine_label = "the token-LM engine"

    def __init__(self, cfg: ModelConfig, *, topk: int, dist=None,
                 n_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 admission_policy: Optional[AdmissionPolicy] = None):
        self.cfg = cfg
        self.topk = topk
        self.n_slots = n_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self._prefill = jax.jit(steps_lib.make_prefill_step(cfg, dist))
        self._recover = jax.jit(
            lambda logits: io_lib.recover_topk(cfg, logits, topk=topk))
        if max_len is None:
            return                      # prefill-only program
        assert n_slots is not None and n_slots >= 1 and max_len >= 2
        template = tf.init_lm_cache(cfg, n_slots, max_len,
                                    dtype=jnp.dtype(cfg.dtype))
        # Under a ``dist`` the pool takes the serving specs and every jit
        # returning pool or slot state pins that layout, so the decode
        # step compiles once for a whole run matrix.
        pool = row = tok = self._slot_layout = None
        if dist is not None:
            specs = sharding_lib.slot_pool_pspecs(cfg, template, dist,
                                                  n_slots)
            pool = jax.tree.map(dist.sharding, specs,
                                is_leaf=lambda x: isinstance(x, P))
            row = dist.sharding(P(dist.batch_axes))
            tok = dist.sharding(P(dist.batch_axes, None))
            template = jax.device_put(template, pool)
            self._slot_layout = (tok, row, row)
        self._pool_template = template
        step_out = {"caches": pool, "topk_scores": tok, "topk_ids": tok}

        def jit(fn, donate, out):
            pin = {} if dist is None else {"out_shardings": out}
            return jax.jit(fn, donate_argnums=donate, **pin)

        # the pool is donated through every decode/insert: the loop
        # never reuses the previous tree, so XLA (where supported)
        # updates the multi-GB cache in place instead of allocating a
        # second pool and copying per step
        decode = steps_lib.make_slot_decode_step(cfg, topk=topk, dist=dist)
        # how the compiled step writes its KV rows, named on its span
        self.kv_write = decode.kv_write
        self._decode = jit(decode, (2,), step_out)
        # degrade ladder (DESIGN.md §14): one pre-built decode jit per
        # stage width; a DEGRADE/RESTORE swaps the dict entry in use.
        # Narrowing the served top-k never changes the emitted token —
        # the next token is the top-1 id, invariant under k.
        self._stage = admission_lib.STAGE_NORMAL
        self._stage_decodes = build_stage_decodes(
            self._decode, topk, admission_policy,
            lambda k: jit(steps_lib.make_slot_decode_step(
                cfg, topk=k, dist=dist), (2,), step_out))
        if dist is not None and dist.n_batch > 1:
            per_shard = n_slots // dist.n_batch
            self._insert = steps_lib.make_sharded_insert(specs, dist,
                                                         per_shard)
            self._compact = steps_lib.make_compact_pool(specs, dist,
                                                        per_shard)
        else:
            self._insert = jit(steps_lib.insert_cache_slot, (0,), pool)
            self._compact = jit(
                lambda caches, perm: jax.tree.map(
                    lambda buf: jnp.take(buf, perm, axis=1), caches),
                (0,), pool)
        # (tokens, pos, active) live ON DEVICE for the whole run, so no
        # decode step uploads them.  Steady-state decode advances them
        # from the step's own outputs (_advance — next token and pos+1
        # for every slot that decoded); the host touches them only on
        # admit (_set_slot), retire (_drop_slot), compaction and a
        # host's death (_remap).  Values are bit-identical to host-side
        # bookkeeping, so tokens are too.
        self._advance = jit(
            lambda ids, tokens, pos, active: (
                jnp.where(active[:, None], ids[:, :1], tokens),
                pos + active.astype(pos.dtype)),
            (1, 2), (tok, row))
        self._set_slot = jit(
            lambda tokens, pos, active, slot, tok, p: (
                tokens.at[slot, 0].set(tok), pos.at[slot].set(p),
                active.at[slot].set(True)),
            (0, 1, 2), self._slot_layout)
        self._drop_slot = jit(lambda active, slot:
                              active.at[slot].set(False), (0,), row)
        # slot i takes slot perm[i]'s vectors, zeroed where ~keep
        self._remap = jit(
            lambda perm, keep, tokens, pos, active: (
                jnp.where(keep[:, None], tokens[perm], 0),
                jnp.where(keep, pos[perm], 0), active[perm] & keep),
            (2, 3, 4), self._slot_layout)

    # -- prefill half --------------------------------------------------
    def prefill(self, params, req: Request, device=None):
        """req -> (caches at prompt length, greedy first token id)."""
        rid = req.rid
        with tracing.span("h2d", what="prompt", bytes=4 * req.prompt_len):
            prompt = jnp.asarray(req.prompt, jnp.int32)
        with tracing.span("launch", fn="expand", rid=rid):
            prompt = prompt[None, :]
        if device is not None:
            prompt = jax.device_put(prompt, device)
        with tracing.span("launch", fn="prefill", rid=rid):
            pre = self._prefill(params, {"tokens": prompt})
        with tracing.span("launch", fn="recover", rid=rid):
            _, ids = self._recover(pre["last_logits"])
        with tracing.span("d2h", what="first", bytes=ids.nbytes):
            ids = np.asarray(ids)
        return pre["caches"], int(ids[0, 0])

    # -- decode half ---------------------------------------------------
    def check_admit(self, req: Request) -> None:
        assert_request_fits(req, self.max_len)

    def stopped(self, req: Request, tok: int) -> bool:
        if self.eos_id is not None and tok == self.eos_id:
            return True
        return len(req.tokens) >= req.max_gen

    def init_state(self, n_slots: int) -> _LMState:
        assert n_slots == self.n_slots
        # copy, not alias: the first donated insert/decode consumes its
        # input buffers, and the template must survive across runs
        state = _LMState(
            caches=jax.tree.map(jnp.copy, self._pool_template),
            tokens=None, pos=None, active=None,
            live=np.zeros((self.n_slots,), bool))
        self.reset_slots(state)
        return state

    def reset_slots(self, state: _LMState) -> None:
        n = self.n_slots
        state.tokens, state.pos, state.active = jax.device_put(
            (jnp.zeros((n, 1), jnp.int32), jnp.zeros((n,), jnp.int32),
             jnp.zeros((n,), bool)), self._slot_layout)
        state.live[:] = False

    def insert(self, state: _LMState, req: Request, payload,
               stats: ServeStats) -> bool:
        small, first = payload
        self.insert_caches(state, req, small)
        req.tokens.append(first)
        stats.tokens_out += 1
        if self.stopped(req, first):
            return False
        self.start(state, req, first)
        return True

    def insert_caches(self, state: _LMState, req: Request, caches
                      ) -> None:
        """``insert``'s first half: the caches into ``req.slot``."""
        with tracing.span("h2d", what="slot", bytes=4):
            slot = jnp.int32(req.slot)
        with tracing.span("launch", fn="insert", rid=req.rid):
            state.caches = self._insert(state.caches, caches, slot)

    def start(self, state: _LMState, req: Request, first: int) -> None:
        """``insert``'s admit half: ``req.slot`` decodes from ``first``."""
        rid = req.rid
        # admit event: three scalars, each uploaded (and converted) on
        # its own
        with tracing.span("h2d", what="slot", bytes=4):
            slot = jnp.int32(req.slot)
        with tracing.span("h2d", what="token", bytes=4):
            tok = jnp.int32(first)
        with tracing.span("h2d", what="pos", bytes=4):
            pos = jnp.int32(req.prompt_len)
        with tracing.span("launch", fn="set_slot", rid=rid):
            state.tokens, state.pos, state.active = self._set_slot(
                state.tokens, state.pos, state.active, slot, tok, pos)
        state.live[req.slot] = True

    def drop(self, state: _LMState, slot: int, rid=None) -> None:
        """Retire ``slot``: it stops decoding from the next step."""
        with tracing.span("h2d", what="slot", bytes=4):
            idx = jnp.int32(slot)
        with tracing.span("launch", fn="drop", rid=rid):
            state.active = self._drop_slot(state.active, idx)
        state.live[slot] = False

    def compact(self, state: _LMState, perm) -> None:
        """Apply a COMPACT remap, ``perm[new] = old`` as
        ``control.plan_compaction`` gives it, to the caches, the slot
        vectors and ``live``."""
        perm = np.asarray(perm, np.int32)
        state.caches = self._compact(state.caches, perm)
        self._remap_slots(state, perm, np.ones(self.n_slots, bool))

    def clear(self, state: _LMState, lo: int, hi: int) -> None:
        """Slots ``[lo, hi)`` stop decoding and hold a fresh pool's zero
        token and position (a dead host's range, DESIGN.md §10)."""
        slots = np.arange(self.n_slots, dtype=np.int32)
        self._remap_slots(state, slots, (slots < lo) | (slots >= hi))

    def _remap_slots(self, state: _LMState, perm, keep) -> None:
        state.tokens, state.pos, state.active = self._remap(
            perm, keep, state.tokens, state.pos, state.active)
        state.live = state.live[perm] & keep

    def set_stage(self, stage: int) -> None:
        if stage not in self._stage_decodes:
            raise RuntimeError(
                f"{self.engine_label}: degrade stage {stage} was not "
                "pre-built — construct the program with the run's "
                "admission_policy (DESIGN.md §14)")
        self._stage = stage

    def step(self, params, state: _LMState):
        live = int(state.live.sum())
        with tracing.span("launch", fn="decode", live=live,
                          kv_write=self.kv_write):
            out = self._stage_decodes[self._stage](
                params, state.tokens, state.caches, state.pos,
                state.active)
        state.caches = out["caches"]
        # steady-state decode: tokens/pos advance on device from the
        # step's own outputs — no host round-trip re-upload.  The d2h
        # token download below is irreducible (the scheduler decides
        # retirement host-side).  The [:, :1] slice happens OUTSIDE
        # _advance so a degraded stage's narrower top-k never re-traces
        # it (the jit always sees a (B, 1) operand).
        with tracing.span("launch", fn="slice_next", live=live):
            nxt = out["topk_ids"][:, :1]
        with tracing.span("launch", fn="advance", live=live):
            state.tokens, state.pos = self._advance(
                nxt, state.tokens, state.pos, state.active)
        with tracing.span("launch", fn="slice_top1", live=live):
            top1 = out["topk_ids"][:, 0]
        with tracing.span("wait", live=live):
            top1.block_until_ready()
        with tracing.span("d2h", what="ids", bytes=top1.nbytes):
            return np.asarray(top1)

    def emit(self, state: _LMState, req: Request, slot: int, out,
             stats: ServeStats) -> bool:
        tok = int(out[slot])
        req.tokens.append(tok)
        stats.tokens_out += 1
        if self.stopped(req, tok):
            self.drop(state, slot, req.rid)
            return True
        return False


class PrefillWorker:
    """Disaggregated prefill: owns a ``SlotProgram``'s jitted callables,
    optionally pinned to a dedicated device (a 1-device mesh slice of
    the serving topology — DESIGN.md §8).

    The worker emits whatever its program's prefill emits — (caches,
    first_token) for the LM program (default), ((logits_row, slot),
    None) for the one-shot retrieval program; the caller inserts the
    payload into its decode pool (for the sharded pool that insert is
    the device-to-device transfer out of the prefill slice).  Splitting
    prefill out of the engine is what lets the sharded engine place it
    on its own slice while the decode pool spans the data axis; the
    single-host engines use the same worker unpinned, so both paths run
    the very same jitted callables.
    """

    def __init__(self, cfg: Optional[ModelConfig], params, *, topk: int,
                 dist=None, device=None,
                 program: Optional[SlotProgram] = None):
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.program = (program if program is not None
                        else LMSlotProgram(cfg, topk=topk, dist=dist))

    def prefill(self, req: Request):
        """req -> the program's slot payload (see class doc)."""
        return self.program.prefill(self.params, req, device=self.device)


class PrefillPool:
    """Prefill *pool*: a FIFO scheduler over N single-slice
    ``PrefillWorker``s (DESIGN.md §9, ROADMAP follow-up b).

    A burst of same-step arrivals used to serialize on the single prefill
    worker — the whole burst head-of-line blocked admission for the
    duration of N prefills.  The pool dispatches queued jobs FIFO to the
    earliest-available worker (a deterministic virtual-time model: each
    worker's clock advances by the job's prompt length), so with W
    workers a burst drains ~W-times faster in prefill-time while the
    step-clock schedule — and therefore every committed bench row and
    every recovered token — is unchanged for ANY W (prefill is B=1
    exact-length on identical replicated weights on every worker; the
    dispatch order is the admission order).

    In this single-process simulation jobs still *execute* sequentially;
    ``stats`` records the dispatch the pool would overlap — per-worker
    job counts, max queue depth, and the summed virtual queue wait
    (``wait_units``, in prompt-length units) that tests assert shrinks as
    workers are added.  A real deployment runs each worker's jitted
    callables on its own mesh slice asynchronously.

    A worker raising mid-prefill no longer loses the request (it used to
    escape the pool and strand the slot): the job retries on the next
    worker, up to ``PREFILL_MAX_ATTEMPTS`` attempts, then surfaces as a
    ``None`` result — the scheduler turns that into a REJECT event
    instead of hanging.  Injected faults (``FailPlan.fail_prefill``)
    raise at the same point a real crash would.
    """

    def __init__(self, cfg: Optional[ModelConfig], params, *, topk: int,
                 n_workers: int = 1, devices=None, dist=None,
                 failpoints: Optional[FailPlan] = None,
                 program: Optional[SlotProgram] = None):
        assert n_workers >= 1
        if devices is None:
            devices = [None]
        # one PrefillWorker (and thus one set of jitted callables) per
        # DISTINCT device: pool slots landing on the same device share
        # it, so a same-device pool never re-traces the prefill step.
        # A shared `program` (the retrieval path) keeps one set of jitted
        # callables for the whole pool — jit re-specializes per device
        # placement on its own.
        by_device = {}
        self.workers = []
        for i in range(n_workers):
            dev = devices[i % len(devices)]
            if dev not in by_device:
                by_device[dev] = PrefillWorker(cfg, params, topk=topk,
                                               dist=dist, device=dev,
                                               program=program)
            self.workers.append(by_device[dev])
        self.n_workers = n_workers
        self.failpoints = failpoints if failpoints else None
        self._fifo: List[Request] = []
        self._busy = [0.0] * n_workers     # virtual per-worker clock
        self.stats = {"jobs": 0, "max_queue_depth": 0, "wait_units": 0.0,
                      "per_worker": [0] * n_workers, "retries": 0,
                      "rejects": 0}

    def submit(self, req: Request) -> None:
        self._fifo.append(req)
        self.stats["max_queue_depth"] = max(self.stats["max_queue_depth"],
                                            len(self._fifo))

    def _attempt(self, req: Request, w0: int,
                 base: float) -> Optional[Tuple[object, int]]:
        """Run ``req``'s prefill with retry-on-another-worker: attempt k
        lands on worker (w0 + k) % n_workers, so a crashed worker's jobs
        migrate off it.  Accounting (virtual clocks, per-worker counts)
        records only the attempt that completed — the failure-free path
        is step-for-step identical to the pre-retry pool.  Returns None
        once the attempt cap is exhausted (the REJECT path)."""
        with tracing.span("prefill", rid=req.rid, items=req.prompt_len):
            for attempt in range(PREFILL_MAX_ATTEMPTS):
                w = (w0 + attempt) % self.n_workers
                try:
                    if (self.failpoints is not None
                            and self.failpoints.prefill_attempt_fails(
                                req.rid, attempt)):
                        raise PrefillFault(
                            f"injected prefill fault: rid {req.rid} "
                            f"attempt {attempt} on worker {w}")
                    res = self.workers[w].prefill(req)
                except PrefillFault:
                    self.stats["retries"] += 1
                    continue
                self.stats["wait_units"] += self._busy[w] - base
                self._busy[w] += float(req.prompt_len)
                self.stats["per_worker"][w] += 1
                self.stats["jobs"] += 1
                return res
            self.stats["rejects"] += 1
            return None

    def drain(self) -> List[Optional[Tuple[object, int]]]:
        """Dispatch every queued job FIFO to the earliest-available
        worker; returns (caches, first_token) per job in submit order —
        None for a job whose every attempt failed."""
        out = []
        base = max(self._busy) if self._fifo else 0.0
        # a fresh burst starts all workers at the same origin: only the
        # waits created by THIS burst count
        self._busy = [base] * self.n_workers
        for req in self._fifo:
            w = min(range(self.n_workers), key=lambda i: (self._busy[i], i))
            out.append(self._attempt(req, w, base))
        self._fifo = []
        return out

    def prefill_all(self, reqs: List[Request]
                    ) -> List[Optional[Tuple[object, int]]]:
        for r in reqs:
            self.submit(r)
        return self.drain()


def run_slot_loop(program: SlotProgram, params, prefill_pool: PrefillPool,
                  requests: List[Request], n_slots: int,
                  state=None, failpoints: Optional[FailPlan] = None,
                  admission_policy: Optional[AdmissionPolicy] = None,
                  ) -> Tuple[Dict[int, Request], ServeStats,
                             Scheduler, object]:
    """THE continuous-batching serve loop, generic over a SlotProgram.

    Admission, prefill dispatch, rejection, per-step stats, clock
    fast-forward and retirement are identical for every program; what a
    slot holds (KV caches vs a logits row), what a decode step computes,
    and what retires a slot (stop condition vs oneshot) live in the
    program.  The LM engine's ``run`` and the retrieval engine's ``run``
    are both thin wrappers over this function — tokens and top-k ids are
    bit-identical to the pre-refactor per-engine loops (asserted by
    tests/test_serving.py + tests/test_retrieval.py and the
    BENCH_serving.json --check gate).

    ``failpoints`` injects overload (DESIGN.md §14) exactly as the
    sharded path does: ``surge:R@S`` compresses the queue's arrival
    clock, ``slow_decode:N@S`` makes each decode step cost N clock
    ticks.  ``admission_policy`` enables the overload pass — shed
    expired / over-bound queued requests, then step the degrade ladder
    — evaluated once per clock tick BEFORE admission, identical in shape
    to ``ShardedScheduler._apply_policy``.  Because this loop serves any
    SlotProgram, the policy lands on the LM and retrieval engines at
    once.

    Mutates and returns the requests; also returns the Scheduler (slot
    event log) and the program state (e.g. the retrieval program's
    accumulated modeled bytes).
    """
    assert_kind(requests, program.kind, program.engine_label)
    fp = failpoints if failpoints else None
    queue = RequestQueue(
        requests,
        arrival_key=(None if fp is None else
                     (lambda r: fp.effective_arrival(r.arrival_step))))
    sched = Scheduler(n_slots)
    stats = ServeStats()
    policy = admission_policy
    window = (deque(maxlen=policy.pressure_window)
              if policy is not None else None)
    stage = admission_lib.STAGE_NORMAL
    policy_stepped = -1
    if state is None:
        state = program.init_state(n_slots)
    now = 0
    t0 = time.perf_counter()

    while len(queue) or sched.n_active:
        if policy is not None and policy_stepped != now:
            # the overload pass, once per clock tick: sheds first, so
            # the pressure sample reflects the bounded queue
            policy_stepped = now
            visible = queue.visible(now)
            sheds = admission_lib.compute_sheds(
                {r.rid: (queue.arrival_of(r), r.home) for r in visible},
                {r.rid: r.deadline_step for r in visible}, now, policy)
            if sheds:
                reasons = dict(sheds)
                for req in queue.remove([rid for rid, _ in sheds]):
                    req.shed = True
                    req.finish_step = now
                    sched.log.shed(now, req.rid, reasons[req.rid],
                                   req.home)
                    stats.sheds += 1
            window.append(admission_lib.pressure(
                len(queue.visible(now)), n_slots))
            new = admission_lib.plan_stage(window, policy, stage)
            if new != stage:
                sched.log.degrade(now, stage, new)
                stats.degrades += 1
                program.set_stage(new)
                stage = new
        admitted = sched.admit(queue, now)
        for req in admitted:
            program.check_admit(req)
        # the whole admission burst goes through the prefill pool at
        # once: FIFO dispatch over the workers, results in admission
        # order (token- and schedule-identical for any worker count)
        prefilled = (prefill_pool.prefill_all(admitted)
                     if admitted else [])
        for req, res in zip(admitted, prefilled):
            if res is None:
                # every prefill attempt failed: REJECT — free the slot
                # instead of hanging the pool on a request that can
                # never start
                stats.rejects += 1
                sched.reject(req.slot, now)
                continue
            stats.prefills += 1
            if not program.insert(state, req, res, stats):
                # prefill-time retirement (max_gen==1 / first-token EOS)
                sched.release(req.slot, now)

        if not sched.n_active:
            nxt = queue.next_arrival()
            if nxt is None:
                break
            if nxt <= now:
                # a slot was freed at `now` (prefill-time retirement or
                # reject) while a request is already ready: re-admit
                # NOW, no clock tick
                continue
            # empty pool: fast-forward the clock to the next arrival
            stats.idle_steps += nxt - now
            now = nxt
            continue

        out = program.step(params, state)
        stats.decode_steps += 1
        stats.slot_steps_total += n_slots
        stats.slot_steps_active += sched.n_active
        # an injected slow_decode makes each decode step cost N clock
        # ticks — arrivals pile up, driving the pressure signal
        now += fp.decode_cost(now) if fp is not None else 1
        for slot, req in list(sched.active.items()):
            if program.emit(state, req, slot, out, stats):
                sched.release(slot, now)

    if stage != admission_lib.STAGE_NORMAL:
        # post-run data-plane reset (like reset_slots): the program is
        # reused across runs and must start the next one undegraded
        program.set_stage(admission_lib.STAGE_NORMAL)
    stats.wall_s = time.perf_counter() - t0
    return {r.rid: r for r in requests}, stats, sched, state


class Engine:
    """Continuous-batching engine over a fixed slot pool.

    One Engine owns ONE ``LMSlotProgram`` — the jitted prefill /
    slot-decode / cache-insert callables and the preallocated pool
    template; ``run`` (continuous, via ``run_slot_loop``) and
    ``run_static`` (A/B baseline) share them, so any numeric difference
    between the two paths would be a scheduling bug, not a compile
    difference.
    """

    @staticmethod
    def supports(cfg: ModelConfig) -> bool:
        """Continuous batching serves decoder-only token LMs; enc-dec
        (audio) and frontend-stub (vlm) archs carry non-token prefill
        inputs the engine does not schedule — they serve via the static
        launch/serve.py path.  Single source for the eligibility rule
        (the CLI checks it before paying for param init)."""
        return cfg.family != "audio" and cfg.frontend == "none"

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int,
                 max_len: int, topk: int = 8,
                 eos_id: Optional[int] = None, dist=None,
                 prefill_workers: int = 1,
                 failpoints: Optional[FailPlan] = None,
                 admission_policy: Optional[AdmissionPolicy] = None):
        if not Engine.supports(cfg):
            raise NotImplementedError(
                f"{cfg.name}: continuous batching serves decoder-only "
                "token LMs (see Engine.supports); use the static "
                "launch/serve.py path")
        assert n_slots >= 1 and max_len >= 2
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.topk = topk
        self.eos_id = eos_id
        self.failpoints = failpoints if failpoints else None
        self.policy = admission_policy
        self.program = LMSlotProgram(cfg, topk=topk, dist=dist,
                                     n_slots=n_slots, max_len=max_len,
                                     eos_id=eos_id,
                                     admission_policy=admission_policy)
        # the pool shares the engine's program: one set of jitted
        # prefill callables for prefill AND admission (jit
        # re-specializes per device placement on its own)
        self.prefill_pool = PrefillPool(cfg, params, topk=topk, dist=dist,
                                        n_workers=prefill_workers,
                                        failpoints=self.failpoints,
                                        program=self.program)

    # ------------------------------------------------------------------
    def run(self, requests: List[Request]
            ) -> Tuple[Dict[int, Request], ServeStats]:
        """Continuous batching: admit into freed slots every step, retire
        on per-slot stop conditions.  Mutates and returns the requests."""
        results, stats, sched, _ = run_slot_loop(
            self.program, self.params, self.prefill_pool, requests,
            self.n_slots, failpoints=self.failpoints,
            admission_policy=self.policy)
        self._sched = sched          # exposed for the simulation tests
        return results, stats

    # ------------------------------------------------------------------
    def run_static(self, requests: List[Request]
                   ) -> Tuple[Dict[int, Request], ServeStats]:
        """Static-batching A/B baseline over the SAME jitted steps.

        Requests are grouped n_slots at a time in arrival order; a group
        starts only when its last member has arrived and drains until its
        longest request stops — retired slots keep burning decode steps,
        which is exactly the utilization gap continuous batching closes.
        """
        assert_kind(requests, "lm", "the token-LM engine")
        prog = self.program
        stats = ServeStats()
        reqs = sorted(requests, key=lambda r: (r.arrival_step, r.rid))
        state = prog.init_state(self.n_slots)
        now = 0
        t0 = time.perf_counter()

        for g in range(0, len(reqs), self.n_slots):
            group = reqs[g:g + self.n_slots]
            start = max([now] + [r.arrival_step for r in group])
            stats.idle_steps += start - now
            now = start

            prog.reset_slots(state)
            # host-side mirror of the active mask — scheduling decisions
            # (group drained? which slots still collect?) stay host-side;
            # the device mask is only written on admit/retire events
            collecting = np.zeros((self.n_slots,), bool)
            for slot, req in enumerate(group):
                req.slot = slot
                req.admitted_step = now
                prog.check_admit(req)
                res, = self.prefill_pool.prefill_all([req])
                assert res is not None, (
                    f"request {req.rid}: prefill permanently failed on "
                    "the static path (no REJECT protocol there — serve "
                    "it via the continuous engine)")
                stats.prefills += 1
                if prog.insert(state, req, res, stats):
                    collecting[slot] = True
                else:
                    req.finish_step = now

            while collecting.any():
                out = prog.step(self.params, state)
                stats.decode_steps += 1
                # static batching burns every slot of the pool per step
                stats.slot_steps_total += self.n_slots
                stats.slot_steps_active += int(collecting.sum())
                now += 1
                for slot, req in enumerate(group):
                    if not collecting[slot]:
                        continue
                    if prog.emit(state, req, slot, out, stats):
                        req.finish_step = now
                        collecting[slot] = False

        stats.wall_s = time.perf_counter() - t0
        return {r.rid: r for r in requests}, stats


def mean_latency(results: Dict[int, Request]) -> float:
    """Mean (finish - arrival) in decode steps across completed requests.
    Shed requests are terminal but never served — no latency to count."""
    done = [r for r in results.values() if r.done and not r.shed]
    if not done:
        return 0.0
    return float(np.mean([r.finish_step - r.arrival_step for r in done]))
