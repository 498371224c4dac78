"""``points_per_s``: the points of all calls completed in the window over
the time from the first call's start to the last call's end. A call that
starts inside ``--seconds`` runs to its end and counts."""


def read(window):
    if not window.calls:
        return None
    span = window.calls[-1][1] - window.calls[0][0]
    return sum(c[2] for c in window.calls) / span
