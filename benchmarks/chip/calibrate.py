#!/usr/bin/env python3
"""Readings for the limits of ``correct``: whole runs of a cell, one per
seed, in one process, with the low-precision control in the program's
place, each reporting ``correct`` as the control makes it and the
program's own readings on the same served requests.

    python3 benchmarks/chip/calibrate.py --workload smollm-360m.chat \\
        --seeds 11,12,13 --seconds 51 --control fp8

The control is the float32 reference with every linear layer's weights and
inputs rounded to float8 e4m3 (one scale per tensor); its ``logit_gap`` is
the widest gap, below the reference's best logit, of the token the control
puts first, at each served position.  ``--control ''`` reads the program
alone.  Benchmark runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="fp8")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        try:
            out = R.run(cell, seed, args.seconds, traced=False,
                        control=args.control)
        except R.NoChip as e:
            R.log(f"[error] {e}")
            return 2
        c = out["checks"]
        print(json.dumps({
            "seed": seed, "control": args.control or None,
            "correct": out["correct"],
            "checks": {k: v for k, v in c.items() if not k.startswith("_")},
            "program_logit_gap": c["_detail"]["program_logit_gap"],
            "detail": c["_detail"],
            "compiles_in_window": out["compiles_in_window"],
            "setup_s": out["setup_s"],
            **{k: v for k, v in out["e2e"].items()}}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
