"""Readings of ``correct``'s numbers over many seeds in one process.

    python chipbench/readings.py --workload <cell> --seconds <s> \
        --seeds 11 12 13 ... [--precision bf16] [--max-iter 5] \
        [--fault stale|half|altered]

For each seed: the cell's set-up and a window of ``--seconds``, exactly
as a run makes them, then the reference's readings, one JSON line per
seed. The programs compile once for all seeds. ``--precision bf16`` runs
the control (the program's bf16 distance path); ``--max-iter`` cuts the
solve's movement iterations; ``--fault`` plants a fault of
``chipbench/faults.py``. This is how the limits in
``chipbench/configs`` were set (the sound runs' largest reading against
the control's and the faults' smallest); the benchmark's runs do not
use it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=("f32", "bf16"), default=None)
    ap.add_argument("--max-iter", type=int, default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import faults, gen, harness, reference
    _, cell, config, traffic = harness.load_cell(ROOT, args.workload)
    os.environ.update({
        "JAX_COMPILATION_CACHE_DIR": os.path.join(ROOT, harness.CACHE_DIR),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"})
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    opts = {}
    if args.precision:
        opts["assign_precision"] = args.precision
    if args.max_iter is not None:
        opts["max_iter"] = args.max_iter
    if args.fault:
        faults.plant(args.fault)
    limits = {"out_of_range": 0, "imbalance": config["epsilon"],
              **config["limits"]}
    for seed in args.seeds:
        t0 = time.perf_counter()
        load = harness.make_load(config, traffic, seed, opts)
        load.setup()
        t_setup = time.perf_counter() - t0
        times, error = harness.run_window(load, args.seconds, False)
        answers = [load.answer(i) for i in range(len(times))]
        sweeps = [load.sweeps(i) for i in range(len(times))]
        iters = [int(r.stats["levels"][0]["iters"]) for r in load.results]
        r = reference.check_calls(answers, config["k"],
                                  gen.derive_seed(seed, 3), limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "opts": opts, "fault": args.fault,
                          "calls": len(times), "error": error,
                          "call_s": [e - s for s, e, _ in times],
                          "setup_s": t_setup, "sweeps": sweeps,
                          "iters": iters, **r,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
