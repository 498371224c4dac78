"""``sweeps.*``: assign sweeps per call, a count from the ``stats`` that
the front door returns (balance iterations of every movement iteration,
warm-up rounds included, the final balance pass, and a warm start's
pre-pass). A repartition step that retried its balance reports only its
last attempt's sweeps."""


def read(run):
    if not run.calls:
        return None
    return sum(c["sweeps"] for c in run.calls) / len(run.calls)
