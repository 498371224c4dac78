"""``host_ms.*``: host time per call, in ms. Each call's span from the
benchmark's own annotation, minus the time inside it when any chip ran
an operation: the front door's host work (SFC bootstrap, permutation,
transfer or deal, scatter-back, migration) and dispatch."""


def read(run):
    if not run.calls:
        return None
    host = [(c["end_ns"] - c["start_ns"])
            - run.trace.busy_ns(c["start_ns"], c["end_ns"])
            for c in run.calls]
    return sum(host) / len(host) * 1e-6
