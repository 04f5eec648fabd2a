"""The control of a cell's comparison: the reference put in the
program's place with one step of the configuration's guarantee taken
away (each query kind's `control`), run through the same comparison on
the same requests the window sent. It has to come out NOT correct. In a
mix that writes, each read's control is taken one acknowledged write
behind (state lo - 1 of `chipbench/history.py`) and judged against the
program's own history of commits.

  python3 -m chipbench.control --workload <cell> --seed <n> --seconds <s>

runs the cell once (a short window will do), then the control and, where
a query kind has them, the `faults` it plants in the program's own
answers (the readings a limit's upper end is set from), and prints one
JSON line with the program's numbers and theirs. The benchmark's own
runs never run it; chipbench/tests/test_chipbench.py keeps it at a size
a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from chipbench import history
from chipbench import run as harness


def summary(numbers: dict) -> dict:
    """The distributions behind the aggregated numbers (for PERF.md's
    readings)."""
    return {n: {"n": len(v), "sum": float(np.sum(v)), "max": float(np.max(v)),
                "min": float(np.min(v)), "median": float(np.median(v)),
                "top": sorted(v, reverse=True)[:5]}
            for n, v in numbers.items() if v}


def judged(state: dict, answers_of) -> dict:
    if state["history"] is None:
        numbers = harness.numbers_of(state["mix"], state["model"],
                                     state["sample"], answers_of=answers_of)
    else:
        numbers = history.numbers(state["mix"], state["kinds"],
                                  state["model"], state["sample"],
                                  state["history"], stale=True)
    checks = harness.judge(state["config"], numbers, 0)
    return {"correct": all(c["ok"] for c in checks.values()),
            "raw": summary(numbers),
            "checks": {n: [c["value"], c["op"], c["limit"]]
                       for n, c in checks.items()}}


def control_numbers(state: dict) -> dict:
    """The control's numbers and, where a query kind plants `faults` in
    the program's own answers (a mix that reads only), each fault's."""
    model, seed = state["model"], state["seed"]
    out = judged(state, lambda kind, params, keys, answers: kind.control(
        model, params, keys))
    out["program_raw"] = summary(state["numbers"])
    names = {name for k in state["kinds"] for name in getattr(k, "FAULTS", ())
             if state["history"] is None}
    out["faults"] = {
        name: judged(state, lambda kind, params, keys, answers: (
            kind.faults(model, params, keys, answers, seed)[name]
            if name in getattr(kind, "FAULTS", ()) else answers,
            state["captured"]))
        for name in sorted(names)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    result = harness.run(args, after=control_numbers)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "rehearsal": result["rehearsal"],
                      "device": result["device"],
                      "program": {"correct": result["correct"],
                                  "checks": result["checks"]},
                      "control": result["after"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
