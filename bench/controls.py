#!/usr/bin/env python3
"""Read a cell's check on the program and on its control, seed by seed, in
one process on the chip.

    python3 bench/controls.py --workload <name> --seconds <s> --seeds <n> ...

For each seed this goes through `run.drive`, the sequence of every
benchmark run (set-up, a short window at the cell's own load, the
program's state freed, the check), and prints one JSON line
with the numbers the check compares for the program and, computed on the
same inputs, for the control (the driver's `control`: the plain reference
in a lower precision, or a path that breaks a stated guarantee).  The
limits in the traffic files are set from these readings.  The benchmark's
own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def read(workload: str, seed: int, seconds: float, *, root: Path = R.ROOT,
         require_tpu: bool = True, peaks: dict | None = None) -> dict:
    t0 = time.perf_counter()
    r = R.drive(workload, seed, seconds, False, root=root,
                require_tpu=require_tpu, peaks=peaks)
    t1 = time.perf_counter()
    ctl = r.cell.driver.control(r.state)
    t2 = time.perf_counter()
    line = {"workload": workload, "seed": seed,
            "program": r.checks, "control": ctl,
            "attempted": r.win["attempted"], "check_s": t1 - t0,
            "control_s": t2 - t1}
    del r
    gc.collect()
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for s in args.seeds:
        print(json.dumps(read(args.workload, s, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
