"""Drive a rehearsal run of one cell with the timed path broken
underneath: an answer altered where it is produced. `correct` has to
come out false (test_chipbench.py, beside this file, runs it in a
process of its own).

  python3 -m chipbench.tests.faults <fault> --workload <cell> --seed <n> ...

A fault is a file, `chipbench/faults/<fault>.py` with one `plant()`, and
a mix's file names the one that has to break it (`fault`). A name with
no file ends the run with no result."""

from __future__ import annotations

import importlib
import json
import sys


def main(argv) -> int:
    fault, rest = argv[0], argv[1:]
    try:
        planted = importlib.import_module(f"chipbench.faults.{fault}")
    except ModuleNotFoundError as e:
        if e.name != f"chipbench.faults.{fault}":
            raise
        raise SystemExit(f"chipbench: no fault {fault!r}: "
                         f"chipbench/faults/{fault}.py is missing ({e})")
    from chipbench import run

    args = run.parser().parse_args(rest + ["--rehearsal"])
    run.load_cell(args)  # its `rehearsal_env`, before the program reads it
    import dgraph_tpu  # noqa: F401  (places the compile cache before jax)

    planted.plant()
    print(json.dumps(run.run(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
