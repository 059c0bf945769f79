"""Data-axis-sharded serving: the sharded *control plane* of the
control/data-plane split (DESIGN.md §8/§9), driving the one LM data
plane, ``engine.LMSlotProgram``, built with the mesh's ``dist``.

The single-host engine's slot pool lives on the local mesh and its
admission is host-side Python.  This module shards exactly that
boundary, the way production recommenders do (DLRM, Naumov et al.
2019):

  * **Sharded slot pool** — the program places its cache tree by
    ``launch/sharding.slot_pool_pspecs``: the slot axis shards over the
    ``data`` mesh axis, each data shard owns a contiguous slot range, so
    decode reads are all-local and a cache insert touches one shard.
    ``(tokens, pos, active)`` stay on the device and advance from each
    step's own outputs, exactly as on one host.
  * **Transported admission** — scheduling is the replicated state
    machine of ``serving/control.py`` orchestrated by
    ``scheduler.ShardedScheduler``: arrival/release deltas travel a
    pluggable ``Transport`` (``"sim"`` — PR 3's in-process gossip,
    log-identical; ``"collective"`` — fixed-size padded all_gather over
    the mesh's data axis, the jax.distributed-ready protocol), every host
    computes the same admission assignment, and each host executes only
    its own slot range — no slot or request is ever claimed twice.
  * **Disaggregated prefill pool** — prefill runs on
    ``engine.PrefillPool``: a FIFO scheduler over N single-device mesh
    slices, so a burst of arrivals no longer head-of-line blocks
    admission behind one worker; the program's shard-local insert (a
    shard_map whose replicated-operand broadcast IS the device-to-device
    transfer) writes the emitted caches into the owning shard.
  * **Slot compaction** — with ``compact_threshold`` set, the control
    plane densifies fragmented host shards (``control.plan_compaction``)
    and the program applies the remap to the cache pytree (a shard-local
    gather, donated in-place update) and to its slot vectors.  The
    densified occupancy feeds ``bloom_decode_topk``'s prefetched
    row-skipping grid, so a scattered pool recovers the dense pool's HBM
    bytes (bench_kernels.py ``.decode_topk.scatter*`` rows, gated in CI).
  * **ONE compiled decode step survives sharding AND compaction** — the
    program's decode jit pins the donated cache layout to the pool
    specs, and the compaction remap preserves it (out_specs == pool
    specs), so the executable never changes mid-run.  The multi-host
    sim test asserts ``program._decode._cache_size() == 1`` after a
    full transport x compaction run matrix.

Per-request tokens are BIT-identical to the single-host engine and to
solo static serving — across both transports and with compaction on or
off: prefill is B=1 at exact prompt length everywhere, every decode op is
row-independent, and a compaction merely permutes rows (asserted by
tests/test_serving_multihost.py on a simulated 8-device topology).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch import sharding as sharding_lib
from repro.serving.admission import AdmissionPolicy, STAGE_NORMAL
from repro.serving.control import (CollectiveTransport, SimTransport,
                                   Transport)
from repro.serving.engine import (Engine, LMSlotProgram, PrefillPool,
                                  ServeStats)
from repro.serving.scheduler import (Request, ScheduleClient,
                                     ShardedScheduler, run_schedule)


class _PoolClient(ScheduleClient):
    """``run_schedule``'s hooks as calls of the engine's ``LMSlotProgram``
    on one run's device-resident state.  The model-free ``_SimClient``
    fills the same hooks with placeholders — sharing the loop is what
    makes the engine's event log equal the simulation's
    integer-for-integer."""

    def __init__(self, engine: "ShardedEngine"):
        self.e = engine
        self.program = engine.program
        # a run starts undegraded, whatever stage the last one ended in
        self.program.set_stage(STAGE_NORMAL)
        self.state = self.program.init_state(engine.n_slots)

    def prefill(self, reqs: List[Request]) -> List[Optional[int]]:
        for req in reqs:
            self.program.check_admit(req)
        firsts = []
        for req, res in zip(reqs,
                            self.e.prefill_pool.prefill_all(reqs)):
            if res is None:
                # attempt cap exhausted: no caches to insert — the loop
                # REJECTs the slot, which stays inactive
                firsts.append(None)
                continue
            caches, first = res
            self.program.insert_caches(self.state, req, caches)
            firsts.append(first)
        return firsts

    def stopped(self, req: Request, tok: int) -> bool:
        return self.program.stopped(req, tok)

    def start_slot(self, req: Request, first: int) -> None:
        self.program.start(self.state, req, first)

    def decode(self, active_map: Dict[int, Request]) -> Dict[int, int]:
        # the step advances every live slot's token and pos on the device
        ids = self.program.step(self.e.params, self.state)
        return {gslot: int(ids[gslot]) for gslot in active_map}

    def set_stage(self, stage: int) -> None:
        self.program.set_stage(stage)

    def stop_slot(self, gslot: int) -> None:
        self.program.drop(self.state, gslot)

    def compact(self, perm: List[int]) -> None:
        self.program.compact(self.state, perm)

    def host_killed(self, host: int) -> None:
        # the dead range stops decoding THIS step: clearing its slots is
        # the data plane's entire epoch change — surviving rows neither
        # recompile (same shapes) nor change values (row independence)
        lo = host * self.e.slots_per_host
        self.program.clear(self.state, lo, lo + self.e.slots_per_host)

    def host_down(self, host: int, reqs: List[Request]) -> None:
        # death is visible cluster-wide: the range holds a fresh pool's
        # zeros for its next occupant (insert overwrites the cache rows)
        self.host_killed(host)


class ShardedEngine:
    """Continuous batching over a data-axis-sharded slot pool.

    ``mesh`` must carry a ``data`` axis; one simulated host per data
    shard, ``slots_per_host`` slots each (global pool = n_hosts *
    slots_per_host slots).  ``run`` consumes per-host workloads
    (``loadgen.sharded_workload``) through the transported admission
    protocol.  Eligibility mirrors ``Engine.supports``.

    ``transport`` / ``compact_threshold`` / ``failpoints`` set the run
    defaults (all overridable per ``run`` call): ``"sim"`` + ``None`` is
    exactly PR 3's behavior; ``"collective"`` exchanges the same deltas
    over a real device all_gather; a float threshold enables slot
    compaction; a ``FailPlan`` replays a deterministic failure schedule
    (host kills, prefill faults, transport hangs, digest corruption)
    against the run — recovery is part of the replicated schedule, so
    the engine's event log still equals the model-free sim's.
    ``prefill_workers`` sizes the prefill pool over single-device slices
    of the mesh (worker i on device i mod n_devices) — the recovered
    tokens are identical for any worker count.
    """

    def __init__(self, cfg: ModelConfig, params, *, mesh,
                 slots_per_host: int, max_len: int, topk: int = 8,
                 eos_id: Optional[int] = None, gossip_delay: int = 1,
                 prefill_device=None, prefill_workers: int = 1,
                 transport: Union[str, Transport] = "sim",
                 compact_threshold: Optional[float] = None,
                 collective_capacity: int = 8,
                 failpoints=None,
                 admission_policy: Optional[AdmissionPolicy] = None):
        if not Engine.supports(cfg):
            raise NotImplementedError(
                f"{cfg.name}: sharded serving covers the same decoder-only "
                "token LMs as Engine (see Engine.supports)")
        assert slots_per_host >= 1 and max_len >= 2
        self.cfg = cfg
        self.mesh = mesh
        self.dist = sharding_lib.DistContext(mesh)
        self.n_hosts = int(self.dist.n_batch)
        self.slots_per_host = slots_per_host
        self.n_slots = self.n_hosts * slots_per_host
        self.max_len = max_len
        self.topk = topk
        self.eos_id = eos_id
        self.gossip_delay = gossip_delay
        self.transport = transport
        self.compact_threshold = compact_threshold
        self.collective_capacity = collective_capacity
        self.failpoints = failpoints if failpoints else None
        self.admission_policy = admission_policy

        # decode-pool weights: explicitly replicated across the mesh so
        # every per-step input is committed and the step compiles once
        self.params = jax.device_put(
            params, jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                 params))

        # Disaggregated prefill pool: each worker owns its OWN weight
        # copy on its own 1-device mesh slice (prefill/decode
        # disaggregation — prefill capacity scales independently of the
        # pool).  In this single-process simulation the slices double as
        # data shards, so those devices carry two param copies; a real
        # deployment passes devices OUTSIDE the decode mesh.  B=1
        # prefill cannot shard, so the slices need no DistContext.
        devices = ([mesh.devices.flat[i % mesh.devices.size]
                    for i in range(prefill_workers)]
                   if prefill_device is None else [prefill_device])
        self.prefill_pool = PrefillPool(cfg, params, topk=topk,
                                        n_workers=prefill_workers,
                                        devices=devices)

        # the data plane: the same LMSlotProgram the single-host engine
        # runs, its pool placed by the slot-pool specs of `dist`
        self.program = LMSlotProgram(
            cfg, topk=topk, dist=self.dist, n_slots=self.n_slots,
            max_len=max_len, eos_id=eos_id,
            admission_policy=admission_policy)

    def _make_transport(self,
                        transport: Union[str, Transport]) -> Transport:
        if isinstance(transport, Transport):
            return transport
        if transport == "sim":
            return SimTransport(self.gossip_delay)
        if transport == "collective":
            from repro.serving.collective import make_device_gather
            return CollectiveTransport(
                self.n_hosts, self.gossip_delay,
                capacity=self.collective_capacity,
                gather=make_device_gather(self.mesh))
        raise ValueError(f"unknown transport {transport!r}")

    # ------------------------------------------------------------------
    def run(self, per_host_requests: List[List[Request]], *,
            transport: Union[str, Transport, None] = None,
            compact_threshold: Union[float, None, str] = "default",
            failpoints="default",
            admission_policy="default",
            ) -> Tuple[Dict[int, Request], ServeStats]:
        """Serve per-host arrival streams through the transported pool.

        The loop is LITERALLY ``scheduler.run_schedule`` — the same
        driver the model-free ``simulate_sharded_schedule`` runs — so
        with ``eos_id=None`` the engine's event log reproduces the
        simulation's log integer-for-integer, COMPACT / reclaim / reject
        events included: a ``FailPlan`` injects the same kills and
        prefill faults into both.
        """
        fp = self.failpoints if failpoints == "default" else (
            failpoints if failpoints else None)
        pol = (self.admission_policy if admission_policy == "default"
               else admission_policy)
        if pol is not None and pol.max_stage > 0 \
                and self.admission_policy is None:
            raise RuntimeError(
                "run() got an admission_policy with degrade stages but "
                "the engine was built without one — stage decode jits "
                "are PRE-BUILT at construction (DESIGN.md §14); pass "
                "admission_policy to ShardedEngine(...)")
        # the prefill pool consults the run's plan (it is engine-owned,
        # so re-point it per run; None restores fault-free behavior)
        self.prefill_pool.failpoints = fp
        sched = ShardedScheduler(
            self.n_hosts, self.slots_per_host, self.gossip_delay,
            transport=self._make_transport(
                self.transport if transport is None else transport),
            compact_threshold=(self.compact_threshold
                               if compact_threshold == "default"
                               else compact_threshold),
            failpoints=fp,
            admission_policy=pol)
        sched.push_workloads(per_host_requests)
        client = _PoolClient(self)
        t0 = time.perf_counter()
        stats = run_schedule(sched, client)
        stats.wall_s = time.perf_counter() - t0
        self._sched = sched          # exposed for the simulation tests
        results = {r.rid: r for reqs in per_host_requests for r in reqs}
        return results, stats
