"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``), and last ``checks``: each number the
reference compared beside its limit. Exits non-zero and prints no result
where JAX finds no TPU, fewer chips than the cell needs, or no
partitioner beside the benchmark.

``--precision bf16`` runs the program's bf16 distance path, the control
that ``correct`` must refuse; the benchmark's own runs leave it unset.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--precision", choices=("f32", "bf16"), default=None,
                    help="override the configuration's precision "
                         "(the control of correct)")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness
    try:
        opts = ({"assign_precision": args.precision} if args.precision
                else None)
        return harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), opts=opts)
    except (harness.Refused, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
