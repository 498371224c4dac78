"""``migration_ms.*``: host time per call, in ms, in the program's
migration accounting: its ``repro.migration`` spans inside each call's
span (the previous labels carried through the identity map, and the
weight that changed blocks or was created)."""
from chipbench.spans import phase_ms


def read(run):
    return phase_ms(run, "repro.migration", "migration_ms")
