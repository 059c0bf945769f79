"""Retrieval-tower training driver (DESIGN.md §12).

Trains the FF tower on the Zipf stream with the serving-consistent Bloom
loss (train/retrieval_trainer.py), serves the TRAINED params through
``RetrievalEngine`` (the generic slot loop) on a fresh eval-seed
workload, and hard-asserts the paper's margin — trained MAP ≫ untrained
MAP — before printing the ``retrieval-train: verified`` marker the CI
train-retrieval job greps.

Fault-tolerant like launch/train.py: ``--ckpt`` checkpoints every N
steps and auto-resumes on rerun; ``--fault-at S`` / ``--failpoints`` go
through the same seeded registry as serving chaos (``train_fault@S``
kills the loop at step S — rerun the identical command to resume).

Examples:
  # one point at the config's m (eval2k default = 1/5 compression)
  PYTHONPATH=src python -m repro.launch.train_retrieval --steps 300

  # the paper's compression/accuracy curve, m/d in {1/1, 1/2, 1/5, 1/10}
  PYTHONPATH=src python -m repro.launch.train_retrieval --sweep

  # chaos drill: crash at step 120, resume from the last checkpoint
  PYTHONPATH=src python -m repro.launch.train_retrieval \
      --ckpt /tmp/rt_ckpt --fault-at 120 ; \
  PYTHONPATH=src python -m repro.launch.train_retrieval \
      --ckpt /tmp/rt_ckpt
"""
from __future__ import annotations

import argparse
import json

from repro.configs.retrieval import get_retrieval_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.failpoints import FailPlan
from repro.train import retrieval_trainer as rt


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="eval2k",
                    help="retrieval config preset (default: eval2k — "
                         "the full-score-eval training scale)")
    ap.add_argument("--m", type=int, default=None,
                    help="override the Bloom output dim (single-point "
                         "mode only; the sweep sets m per ratio)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--pairs", type=int, default=512,
                    help="training pairs drawn from the Zipf stream")
    ap.add_argument("--eval-requests", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0,
                    help="grad-accumulation chunks (0 = off)")
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--seed", type=int, default=0,
                    help="training-data seed (eval always uses seed+1)")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (enables resume-on-rerun)")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--fault-at", type=int, default=-1,
                    help="induce a crash at this train step (sugar for "
                         "--failpoints train_fault@S)")
    ap.add_argument("--failpoints", default=None,
                    help="failpoint spec (serving/failpoints.py grammar)")
    ap.add_argument("--table-dtype", default=None,
                    choices=["auto", "float32", "bfloat16", "int8",
                             "fp8_e4m3"],
                    help="pool-logits storage dtype for the recover "
                         "decode (DESIGN.md §13; auto = legacy f32). "
                         "The eval decodes through this knob; the sweep "
                         "additionally reports int8 dual-eval retention "
                         "regardless")
    ap.add_argument("--sweep", action="store_true",
                    help="run the m/d in {1/1, 1/2, 1/5, 1/10} "
                         "compression sweep instead of a single point")
    ap.add_argument("--min-margin", type=float, default=3.0,
                    help="required trained/untrained MAP ratio at 1/5 "
                         "compression (the ISSUE-8 acceptance bar)")
    ap.add_argument("--out", default=None, help="write the report JSON")
    args = ap.parse_args()

    over = {"m": args.m} if args.m else {}
    if args.table_dtype is not None:
        over["table_dtype"] = args.table_dtype
    base = get_retrieval_config(args.config, **over)
    tc = rt.default_train_config(
        steps=args.steps, microbatch=args.microbatch,
        checkpoint_every=(args.checkpoint_every if args.ckpt else 0),
        learning_rate=args.lr)
    plan = FailPlan.parse(args.failpoints)
    if args.fault_at >= 0:
        plan = plan.merge(FailPlan.parse(f"train_fault@{args.fault_at}"))
    failpoints = plan if (args.failpoints or args.fault_at >= 0) else None

    if args.sweep:
        rows = rt.compression_sweep(
            base, tc, n_pairs=args.pairs, batch_size=args.batch,
            n_eval=args.eval_requests, n_slots=args.slots,
            data_seed=args.seed, eval_seed=args.seed + 1)
        rt.assert_trained_margin(rows, min_ratio_at_5=args.min_margin)
        report = {"sweep": rows}
        head = rows[0]
    else:
        row = rt.train_and_eval_point(
            base, tc, n_pairs=args.pairs, batch_size=args.batch,
            n_eval=args.eval_requests, n_slots=args.slots,
            data_seed=args.seed, eval_seed=args.seed + 1,
            checkpoint_dir=args.ckpt, failpoints=failpoints)
        assert row["map"] > row["untrained_map"], (
            f"trained MAP {row['map']:.4f} <= untrained "
            f"{row['untrained_map']:.4f} — training is not helping")
        report = {"point": row}
        head = row

    report["verified"] = True
    print(json.dumps(report, indent=1, sort_keys=True))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(f"retrieval-train: verified ({head['config']}: d={head['d']}, "
          f"{head['steps']} steps, trained map {head['map']:.4f} vs "
          f"untrained {head['untrained_map']:.4f})")


if __name__ == "__main__":
    main()
