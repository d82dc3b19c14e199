#!/usr/bin/env python3
"""The control of a cell's comparison: the reference one precision down.

    python3 benchmarks/chip/control.py --workload testbed.splice_mc \
        --seeds 11 12 13

For each seed it makes the inputs of the cell's compared calls as a run
with that seed would, puts the reference computed at ``Precision.HIGH``
(three bfloat16 passes, see ``chipbench/reference.py``) in the program's
place, and runs the cell's comparison against the reference at the
configuration's precision.  A sound limit makes every seed come out not
correct.  It prints one JSON line per seed with each number and its
limit, and exits non-zero if any seed came out correct.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import compare, generator, reference  # noqa: E402
from chipbench.spec import Spec  # noqa: E402


def control_gaps(cell, seed: int) -> list:
    """The compared numbers of the control, call by call.  Where the mix
    has a guard, the control makes its own trips and rotations from its
    own state, and where the controller decides, its own pulses from its
    own readout; the reference replays them as it would the program's."""
    cfg, tr, kinds = cell.config, cell.traffic, cell.kinds
    out = []
    for k in range(int(tr["check_calls"])):
        ppm = generator.draws(cfg, tr, kinds, seed, k)
        got = reference.simulate(cfg, tr, kinds, ppm, precision="high")
        out.append(compare.check_call(cfg, tr, kinds, ppm, got,
                                      cell.limits)[0])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = Spec(HERE.parents[1]).cell(args.workload)
    passed = 0
    for seed in args.seeds:
        correct, failed, checks = compare.judge(control_gaps(cell, seed),
                                                cell.limits)
        passed += correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct, "checks": checks}),
              flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
