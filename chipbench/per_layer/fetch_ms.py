"""``fetch_ms.*``: host time per call, in ms, in the program's fetch:
its ``repro.fetch`` spans inside each call's span (labels, centers,
influence and stats from the chip to the host, and the labels'
scatter back into the caller's point order)."""
from chipbench.spans import phase_ms


def read(run):
    return phase_ms(run, "repro.fetch", "fetch_ms")
