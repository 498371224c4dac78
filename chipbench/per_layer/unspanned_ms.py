"""``unspanned_ms.*``: time per call, in ms, inside the call's span that
neither a chip op nor any of the program's four phase spans
(``repro.bootstrap``, ``repro.stage``, ``repro.solve``, ``repro.fetch``)
covers: how much of the call the spans leave unnamed.

Says on stderr the host split of a call (each phase's time, and the
host time inside ``repro.solve``, beside ``host_ms``) and the chip's
idle time by the innermost ``repro.*`` span over each gap."""
import sys

from chipbench import spans as program_spans
from chipbench import tracefile


def read(run):
    spans = program_spans.for_run(run) if run.calls else None
    if spans is None or not any(spans.named(p)
                                for p in program_spans.PHASES):
        print("unspanned_ms: no phase span in the trace", file=sys.stderr)
        return None
    red = run.trace
    busy = tracefile.merge(iv for b in red.busy.values() for iv in b)
    n = len(run.calls)
    split = {p: 0.0 for p in program_spans.PHASES}
    unspanned = host = solve_host = 0.0
    for c in run.calls:
        s, e = c["start_ns"], c["end_ns"]
        unspanned += spans.unspanned_ns(s, e, busy)
        host += (e - s) - red.busy_ns(s, e)
        for p in program_spans.PHASES:
            split[p] += spans.ns(p, s, e)
        for sv in spans.named("repro.solve"):
            lo, hi = max(sv.start, s), min(sv.end, e)
            if hi > lo:
                solve_host += (hi - lo) - red.busy_ns(lo, hi)
    print(f"unspanned_ms: host split, ms a call: "
          f"{ {p: v / n * 1e-6 for p, v in split.items()} }, host inside "
          f"repro.solve {solve_host / n * 1e-6}, unspanned "
          f"{unspanned / n * 1e-6}, host_ms {host / n * 1e-6}",
          file=sys.stderr)
    print(f"unspanned_ms: idle by span: "
          f"{program_spans.idle_by_span(red, spans)}", file=sys.stderr)
    return unspanned / n * 1e-6
