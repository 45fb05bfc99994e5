#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 bench/control.py --workload <cell> --seconds <s> \\
        --seeds <a,b,...> [--controls int8,bfloat16]

Runs the cell as ``bench/run.py`` does, once per seed, in one process
(the compiled Program is kept between seeds), and after each window
reads every number the kind's comparison gives for the program and,
on the same prompts and served outputs, for each control: the plain
reference computed in a lower precision (``bench/refs/quant.py``).
Prints one JSON line per seed.  The benchmark's own runs do not run
the controls.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(run.REPO / "src"))
    devs = run.devices(cell.chips)
    run.use_compile_cache()
    controls = tuple(c for c in args.controls.split(",") if c)
    shared: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run_cell(cell, seed, args.seconds, False, devs,
                              controls=controls, shared=shared)
        line = {"seed": seed, "correct": result["correct"],
                "attempted": result["attempted"],
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "readings": shared["readings"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
