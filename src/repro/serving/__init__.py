"""Continuous-batching serving engine (DESIGN.md §7–§9).

control.py      — control plane: pure replicated state machine
                  (apply_deltas/compute_admissions), membership + epochs
                  (HOST_DOWN reclaim/re-queue), compaction planning,
                  the shared EventLog + replay helper, and the Transport
                  implementations (SimTransport, CollectiveTransport)
                  with per-round digest checks + deadlines
failpoints.py   — seeded deterministic fault injection (FailPlan): one
                  spec string replays the identical failure schedule in
                  the engine, the model-free sim, the bench and CI
collective.py   — the device all_gather behind CollectiveTransport
admission.py    — overload policy (DESIGN.md §14): AdmissionPolicy,
                  deadline/bounded-queue shedding (compute_sheds), the
                  windowed pressure signal and the degrade ladder
                  (plan_stage/stage_topk) — pure functions of replicated
                  state, JAX-free like control.py
scheduler.py    — JAX-free RequestQueue/Scheduler (slot admission policy),
                  ShardedScheduler (transported multi-host admission),
                  and run_schedule — the ONE serve loop shared by the
                  sharded engine and the model-free simulation
loadgen.py      — deterministic Poisson arrival + length-mix workloads,
                  per-host streams pure in (seed, host_id)
engine.py       — the slot-pool engine, the disaggregated PrefillPool
                  (FIFO over N mesh-slice workers), the SlotProgram
                  per-slot program protocol, LMSlotProgram (the one LM
                  data plane, sharded under a dist), and the
                  static-batching A/B baseline
sharded_pool.py — ShardedEngine: the sharded control plane driving
                  LMSlotProgram over a data-axis-sharded slot pool
retrieval.py    — web-scale one-shot Bloom retrieval over the same slot
                  pool: Zipf item lookups, streaming Eq. 3 top-k over a
                  10M+-item catalog, modeled-bytes audit vs the
                  dense-table oracle (DESIGN.md §11)
"""
from repro.serving.admission import (MAX_STAGE, SHED_DEADLINE,
                                     SHED_QUEUE_FULL, STAGE_MIN,
                                     STAGE_NARROW, STAGE_NORMAL,
                                     AdmissionPolicy, compute_sheds,
                                     plan_stage, pressure, slo_attainment,
                                     stage_topk)
from repro.serving.control import (CollectiveTransport, ControlState,
                                   Delta, EventLog, SimTransport,
                                   Transport, apply_deltas,
                                   compute_admissions, plan_compaction,
                                   replay_slot_log)
from repro.serving.control import (HOST_DOWN, ReplicaDivergence,
                                   TransportTimeout, control_digest)
from repro.serving.engine import Engine, LMSlotProgram, PrefillFault, \
    PrefillPool, PrefillWorker, ServeStats, SlotProgram, mean_latency
from repro.serving.failpoints import (FailPlan, Failpoint,
                                      PREFILL_MAX_ATTEMPTS)
from repro.serving.loadgen import (LoadSpec, RetrievalLoadSpec,
                                   assert_fresh_instances, burst_workload,
                                   host_stream, make_workload,
                                   merge_workloads, mixed_length_workload,
                                   overload_workload, retrieval_workload,
                                   sharded_workload)
from repro.serving.retrieval import (RetrievalEngine, RetrievalProgram,
                                     evaluate_retrieval,
                                     init_retrieval_params)
from repro.serving.scheduler import Request, RequestQueue, ScheduleClient, \
    Scheduler, ShardedScheduler, run_schedule, simulate_sharded_schedule
from repro.serving.sharded_pool import ShardedEngine

__all__ = ["Engine", "PrefillPool", "PrefillWorker", "ServeStats",
           "SlotProgram", "LMSlotProgram", "mean_latency", "LoadSpec",
           "burst_workload", "host_stream", "assert_fresh_instances",
           "make_workload", "merge_workloads", "mixed_length_workload",
           "sharded_workload", "RetrievalLoadSpec", "retrieval_workload",
           "RetrievalEngine", "RetrievalProgram", "evaluate_retrieval",
           "init_retrieval_params",
           "Request", "RequestQueue", "ScheduleClient",
           "Scheduler", "ShardedEngine", "ShardedScheduler",
           "run_schedule", "simulate_sharded_schedule",
           "CollectiveTransport", "ControlState", "Delta", "EventLog",
           "SimTransport", "Transport", "apply_deltas",
           "compute_admissions", "plan_compaction", "replay_slot_log",
           "FailPlan", "Failpoint", "PREFILL_MAX_ATTEMPTS",
           "PrefillFault", "HOST_DOWN", "ReplicaDivergence",
           "TransportTimeout", "control_digest",
           "AdmissionPolicy", "compute_sheds", "plan_stage", "pressure",
           "slo_attainment", "stage_topk", "overload_workload",
           "MAX_STAGE", "SHED_DEADLINE", "SHED_QUEUE_FULL",
           "STAGE_NORMAL", "STAGE_NARROW", "STAGE_MIN"]
