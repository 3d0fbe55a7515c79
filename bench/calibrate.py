"""Readings that set a cell's limits (``configs[].limits``), on the chip.

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3 ...

In one process, for each seed: a run of the cell as ``bench/run.py`` makes
it (weights from the seed, warm-up, a window of ``--seconds`` at the cell's
load), then the comparison that decides ``correct``, read twice over the
same sample: the served tokens against the reference (the program's
reading), and the tokens that the reference computed in fp8 puts first
(the control's reading), each through the harness's own ``verdict``.  One
JSON line per seed: the program's ``correct`` and ``checks``, and the
control's.  The limit lies above the largest program reading and below the
smallest control reading (see ``PERF.md``).  The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.use_compile_cache()
    devices = run.jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU; nothing was run", file=sys.stderr)
        return 2
    for seed in args.seeds:
        lines = []

        def log(line: str) -> None:
            lines.append(line)
            print(line, file=sys.stderr, flush=True)
        out = run.run_cell(cell, seed, args.seconds, False,
                           devices[:cell["chips"]], log, control=True)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "attempted": out["attempted"], "failed": out["failed"],
            "checks": out["checks"], "control": out["control"],
            "check": [x for x in lines
                      if x.startswith(("check:", "control:"))]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
