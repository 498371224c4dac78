"""``warm_roofline.*``: the assign kernel's share of its roofline on the
warm path, in %, where every call is a ``repartition()`` step over its
own point count.

``assign_roofline``'s reading with each step's sweeps priced at that
step's own ``n``, read from the ``n`` argument of the program's
``repro.repartition`` span inside the call's span, in place of the
configuration's. Padding is not counted, so the share reads low by the
pad share. Where the trace holds no such span, or none of the kernel's
names, the metric is left out of the line.
"""
import sys
import types

from chipbench import plugins
from chipbench.spans import for_run

assign_roofline = plugins.find("per_layer", "assign_roofline", "read")
call_arg = plugins.find("per_layer", "pad_share", "call_arg")


def read(run):
    spans = for_run(run) if run.calls else None
    ns = [None if spans is None
          else call_arg(spans, "repro.repartition", "n", c)
          for c in run.calls]
    if not ns or None in ns:
        print("warm_roofline: a call has no repro.repartition span with "
              "its n", file=sys.stderr)
        return None
    print(f"warm_roofline: n per step {ns}", file=sys.stderr)
    steps = types.SimpleNamespace(
        trace=run.trace, device_kind=run.device_kind,
        calls=[{**c, "n": n} for c, n in zip(run.calls, ns)])
    return assign_roofline(steps)
