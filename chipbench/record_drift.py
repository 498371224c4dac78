"""Record on the chip the small trace of the drift cell that the tests of
its readers read.

    python chipbench/record_drift.py --out chipbench/testdata/drift_v5e.xplane.pb.gz

The drift load (``chipbench/loads/drift.py``) at 2^16 + 42 points of
frame 0 and k = 64: its set-up untraced (the cold call and the warm
steps that compile every bucket), then ``--calls`` window calls, each
inside the benchmark's call span (``chipbench.call``), traced with the
Python tracer off. Writes the trace gzipped, and beside it
(``<out>.json``) each call's point count and sweeps, the inputs of the
roofline reader. Exits 2 without a TPU.
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "drift.refined2d-n5824554-k64"
N = (1 << 16) + 42
SEED = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--calls", type=int, default=3)
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("record_drift: no TPU", file=sys.stderr)
        return 2
    _, _, config, traffic = harness.load_cell(ROOT, CELL)
    load = harness.make_load({**config, "n": N}, traffic, SEED)
    load.setup()
    tdir = tempfile.mkdtemp(prefix="record-drift-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    points = []
    for i in range(args.calls):
        with jax.profiler.TraceAnnotation(harness.SPAN, index=i):
            points.append(load.call(i))
    jax.profiler.stop_trace()
    (src,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                       recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(src, "rb") as f, gzip.open(args.out, "wb") as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tdir, ignore_errors=True)
    side = {"cell": CELL, "seed": SEED, "n": points,
            "k": config["k"], "d": config["d"],
            "sweeps": [load.sweeps(i) for i in range(args.calls)],
            "bytes": os.path.getsize(args.out)}
    with open(args.out + ".json", "w") as f:
        json.dump(side, f)
    print(json.dumps(side))
    return 0


if __name__ == "__main__":
    sys.exit(main())
