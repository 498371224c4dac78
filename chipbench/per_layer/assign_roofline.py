"""``assign_roofline.*``: the assign kernel's share of its roofline, in %.

The least time of every sweep the calls ran (``chipbench.roofline``:
the larger of the cross-term FLOPs at the MXU peak and the bytes of
coordinates, weight and label at the HBM peak, with no padding and no
pruning counted), summed, over the kernel's device time in the trace,
summed over the chips. The kernel is found by the names below; where
the trace holds none of them the metric is left out of the line.
"""
import sys

from chipbench.roofline import sweep_least_seconds

#: short HLO names of the assign kernel's ``pallas_call``
KERNEL_NAMES = ("assign_reduce_pallas", "assign_argmin_pallas")


def read(run):
    kernel_ns, count = run.trace.op_ns(KERNEL_NAMES)
    if not kernel_ns:
        print(f"assign_roofline: no op named {KERNEL_NAMES} in the trace",
              file=sys.stderr)
        return None
    least = 0.0
    for c in run.calls:
        t, bound = sweep_least_seconds(c["n"], c["k"], c["d"],
                                       run.device_kind)
        least += c["sweeps"] * t
    sweeps = sum(c["sweeps"] for c in run.calls)
    print(f"assign_roofline: {bound} bound; {sweeps} sweeps by the stats, "
          f"{count} kernel ops in the trace, {kernel_ns * 1e-9} s of "
          f"kernel time", file=sys.stderr)
    return 100.0 * least / (kernel_ns * 1e-9)
