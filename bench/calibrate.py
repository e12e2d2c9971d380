"""Readings that the limits of a cell are set from, in one process.

    python3 bench/calibrate.py --workload <name> [--seeds 1,2,3] \
        [--control-seeds 4,5,6] [--seconds 30] --out <directory>

For every seed of ``--seeds`` it builds the cell as a run does, drives a
window of ``--seconds`` at the cell's own load and sizes, and compares
what the program produced with the reference, as ``bench/run.py`` does:
those are the lower readings. For every seed of ``--control-seeds`` it
puts the reference, computed one precision step below the
configuration's (``high``: three bfloat16 passes for float32 at
``highest``), in the program's place, on the work a window of
``--seconds`` holds, without running the program: those are the upper
readings. Every reading is printed as a JSON line and written to
``<out>/<workload>.json``. Like a run, it needs the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, seeds, control_seeds, seconds: float, control: str = "high",
             require_chip: bool = True, on_reading=print):
    """One dict per seed: the program's numbers (seeds of ``seeds``) and
    the control's (seeds of ``control_seeds``), None where not read."""
    import jax

    from bench import run

    if require_chip:
        why = run.check_chip(jax, cell.chips)
        if why:
            raise SystemExit(f"calibrate: {why}")
        run.use_cache(jax)
    sys.path.insert(0, str(ROOT / "src"))
    runner = run.runner_of(cell)
    out = []
    for seed in list(seeds) + [s for s in control_seeds if s not in seeds]:
        t0 = time.perf_counter()
        ctx = run.context(cell, seed)
        row = {"seed": seed, "program": None, "control": None}
        if seed in seeds:
            state = runner.setup(ctx)
            win = runner.window(state, seconds, lambda name: contextlib.nullcontext(),
                                contextlib.nullcontext)
            row["run_s"] = time.perf_counter() - t0
            row.update(program=dict(runner.check(state, ctx)[0]), units=win.units)
            del state
        if seed in control_seeds:
            t1 = time.perf_counter()
            row["control"] = dict(runner.control(ctx, control, seconds))
            row["control_s"] = time.perf_counter() - t1
        on_reading(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True, help="directory for <workload>.json")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run

    cell = run.find_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = readings(cell, seeds, ctl, args.seconds, on_reading=lambda r: print(r, flush=True))
    Path(args.out).mkdir(parents=True, exist_ok=True)
    (Path(args.out) / f"{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
