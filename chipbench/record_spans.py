"""Record on the chip the small trace that the span tests read.

    python chipbench/record_spans.py --out chipbench/testdata/spans_v5e.xplane.pb.gz

One cold ``partition()`` of 2^16 + 42 points of the refined layout at
k = 64 inside the benchmark's call span (``chipbench.call``), then one
``repartition()`` step under a drifted weight field inside
``chipbench.step``, traced with the Python tracer off, after an untraced
pass of both has compiled them. Writes the trace gzipped, and prints
one JSON line: the cold call's sweeps (the roofline reader's input) and
the step's balance retries. Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = (1 << 16) + 42
K = 64
SEED = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax
    import numpy as np

    from chipbench.layouts.refined import points
    from chipbench.load import Load
    from repro.partition import PartitionProblem, partition, repartition

    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 2
    pts = points(N, np.random.SeedSequence(SEED))
    prob = PartitionProblem(points=pts, k=K, epsilon=0.03, seed=SEED)
    hot = np.exp(-np.sum((pts - 0.3) ** 2, axis=1) / 0.02)
    step = prob.replace(weights=1.0 + 4.0 * hot)
    prev = partition(prob)                       # compiles both solves
    repartition(step, prev)
    tdir = tempfile.mkdtemp(prefix="record-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.call", index=0):
        cold = partition(prob)
    with jax.profiler.TraceAnnotation("chipbench.step", index=0):
        warm = repartition(step, cold)
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(src, "rb") as f, gzip.open(args.out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tdir, ignore_errors=True)
    sweeps = Load.sweeps(types.SimpleNamespace(results=[cold]), 0)
    print(json.dumps({"n": N, "k": K, "sweeps": sweeps,
                      "balance_retries": warm.stats["balance_retries"],
                      "bytes": os.path.getsize(args.out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
