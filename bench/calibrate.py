"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 4,5,6]

For each seed it prints the numbers the cell's comparison with the
reference reads on the program (the lower readings): a serving cell
serves a window of its own mix at its own rate.  For each control seed
it prints the same numbers for the control, the configuration computed
one precision below the one it states (``control_readings`` of the
cell's module in ``bench/drivers/``); these have to read above the
limit.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, traffic_gen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    spec = harness.benchmark_spec(ROOT)
    cell = harness.find(spec["workloads"], args.workload, "workload")
    config = harness.load_json(ROOT / harness.find(
        spec["configs"], cell["config"], "config")["file"])
    traffic = traffic_gen.load(cell["traffic"], harness.BENCH_DIR / "traffic")
    harness.device_check(cell["chips"])
    harness.configure_jax(ROOT)
    sysmod = harness.load_module(
        harness.BENCH_DIR / "systems" / f"{config['system']}.py",
        f"bench_system_{config['system']}")
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{traffic['driver']}.py",
        f"bench_driver_{traffic['driver']}")
    for side, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in (int(s) for s in seeds.split(",") if s):
            t0 = time.perf_counter()
            if side == "program":
                rows = [("program", driver.readings(
                    sysmod, config, traffic, seed, args.seconds))]
            else:
                rows = driver.control_readings(sysmod, config, traffic,
                                               seed, args.seconds)
            for what, nums in rows:
                print(json.dumps({"seed": seed, "side": what, **dict(nums),
                                  "s": time.perf_counter() - t0}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
