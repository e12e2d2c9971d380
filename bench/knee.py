"""Find the knee of a serve cell: the highest offered rate it sustains.

    python3 bench/knee.py --workload emnist_paper.serve_k50 --seed 1 \
        --rates 20000,30000,40000 [--seconds 30]

One set-up, then one window per rate, in the order given, each with the
cell's own runner. A rate is sustained when no request was refused, the
queue did not grow (the median latency of the window's last fifth of
requests is not above that of its first fifth by more than the first
fifth's median, about one flush), and p99 is within ``deadline_ms`` (20
ms, the premium deadline of the repo's fleet). The cell's
``rate_per_s`` is four fifths of the highest rate sustained. Each
window is printed as one JSON line. Like a run, it needs the chip.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--deadline-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import run

    cell = run.find_cell(args.workload)
    why = run.check_chip(jax, cell.chips)
    if why:
        print(f"knee: {why}", file=sys.stderr)
        return 1
    run.use_cache(jax)
    sys.path.insert(0, str(ROOT / "src"))
    runner = run.runner_of(cell)
    state = runner.setup(run.context(cell, args.seed))
    for rate in (float(r) for r in args.rates.split(",")):
        state.rate = rate
        lat = {}
        win = runner.window(state, args.seconds, lambda n: contextlib.nullcontext(),
                            contextlib.nullcontext, latencies=lat)
        ms = lat["ms"]
        fifth = max(len(ms) // 5, 1)
        first = float(np.median(ms[:fifth]))
        growth = float(np.median(ms[-fifth:])) - first
        p99 = win.metrics["serve_p99_ms"]
        ok = win.failed == 0 and growth <= first and p99 <= args.deadline_ms
        print(json.dumps({"rate": rate, "p50_ms": win.metrics["serve_p50_ms"], "p99_ms": p99,
                          "growth_ms": growth, "failed": win.failed, "requests": win.attempted,
                          "rows_per_batch": win.stats["scored_rows"] / max(win.stats["batches"], 1),
                          "late_ms": win.generator_late_s * 1e3,
                          "gc_pauses_ms": win.gc_pauses_ms[:20],
                          "sustained": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
