"""``stage_ms.*``: host time per call, in ms, in the program's staging:
its ``repro.stage`` spans inside each call's span (the permutation, the
gather and cast of points and weights, and their transfer to the chip
with the initial centers, up to the arrays' being ready there)."""
from chipbench.spans import phase_ms


def read(run):
    return phase_ms(run, "repro.stage", "stage_ms")
