"""The control of a cell's check, at the cell's own size.

    python3 bench/control.py --workload noc6x6.fig9 --seeds 101 102 103

For each seed, the points a run with that seed would compare (one step's
sample) are simulated twice by the plain reference: once as the
configuration states it, and once with its epoch layer computed in bfloat16
(the control, in the program's place).  The check's numbers for the control
are printed per seed beside their limits; the control has to fail the check
on every seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.run import Bench, log

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    entry = bench.entry(traffic["entry"])

    readings = {}
    for seed in args.seeds:
        runner = entry.Runner(config, traffic, chips=cell["chips"], seed=seed)
        step = entry.Step(1, entry.step_seed(seed, 1), [], 0, 0, 0)
        pairs = [(step.seed, i) for _, i in runner.sample([step])]
        t0 = time.perf_counter()
        want = runner.reference(pairs)
        got = runner.reference(pairs, lowp=True)
        verdict = entry.judge([entry.compare(a, b) for a, b in zip(got, want)])
        readings[seed] = {k: v for k, (v, _) in verdict["compared"].items()}
        log(f"[control] seed={seed} {len(pairs)} points in "
            f"{time.perf_counter() - t0:.3f} s: " + ", ".join(
                f"{k}={v} limit={lim}"
                for k, (v, lim) in verdict["compared"].items())
            + f"; correct={verdict['correct']}")
    print(json.dumps({"workload": args.workload, "control": "bfloat16",
                      "readings": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
