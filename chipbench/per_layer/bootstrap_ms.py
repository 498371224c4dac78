"""``bootstrap_ms.*``: host time per call, in ms, in the program's SFC
bootstrap: its ``repro.bootstrap`` spans inside each call's span (the
float64 copy of the points, the Hilbert keys and their sort, the
initial centers). Says on stderr how much of it the keys
(``repro.bootstrap.keys``) and the sort (``repro.bootstrap.sort``)
took."""
import sys

from chipbench.spans import phase_ms


def read(run):
    ms = phase_ms(run, "repro.bootstrap", "bootstrap_ms")
    if ms is not None:
        parts = {p: phase_ms(run, f"repro.bootstrap.{p}", "bootstrap_ms")
                 for p in ("keys", "sort")}
        print(f"bootstrap_ms: {ms} ms a call, of it (ms) {parts}",
              file=sys.stderr)
    return ms
