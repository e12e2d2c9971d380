"""Record the small trace that ``test_bench_trace_reduce.py`` reads.

    python3 bench/record_trace.py bench/testdata/small.xplane.pb

On the chip: trains a 40-device federation with the program's engine
(``batched_rbf_gram`` and SDCA) and serves eight flushes of a 5-member
ensemble (``ensemble_score``), all under one ``bench.traced`` annotation,
with Python frames off, as ``bench/run.py --trace 1`` records. Writes
the ``.xplane.pb`` and prints the perf_counter span of the annotation.
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    out = Path((argv or sys.argv[1:])[0])
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    from bench import run
    from bench.configs import table_gaussian
    from repro.comm import ModelExchange
    from repro.core import Ensemble
    from repro.data.federated import DeviceData, FederatedDataset
    from repro.serve import EnsembleScorer, ServeConfig
    from repro.sim import train_population

    why = run.check_chip(jax, 1)
    if why:
        print(f"record_trace: {why}", file=sys.stderr)
        return 1
    cfg = dict(json.loads((ROOT / "bench/configs/emnist_paper.json").read_text()),
               n_devices=40, total_samples=4000, max_per_device=200)
    devices = table_gaussian.make(cfg, 1)
    ds = FederatedDataset("emnist", [DeviceData(x, y) for x, y in devices], 60, 32)
    train_population(ds, seed=1)                      # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.traced"):
        t0 = time.perf_counter()
        outs = train_population(ds, seed=1).outcomes
        ex = ModelExchange({o.device_id: o.model for o in outs}, [o.report for o in outs])
        sched = EnsembleScorer(Ensemble([ex.received(i) for i in ex.pick("cv", 5, 1)])
                               ).scheduler(ServeConfig(max_batch=32, buckets=(8, 32)))
        rows = np.concatenate([x for x, _ in devices])[:64]
        for i in range(8):
            with jax.profiler.TraceAnnotation("bench.flush"):
                sched.run(list(rows[: 4 + 4 * i]))
            time.sleep(0.002)
        t1 = time.perf_counter()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"span": [t0, t1], "bytes": out.stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
