"""``pad_share.*``: the share of the warm solve's slots that are
padding, in %: over the window's steps, the sum of ``slots - n`` over
the sum of ``slots``, where ``slots`` is the ``slots`` argument of the
program's ``repro.solve`` span in the step (the point count it solved
over) and ``n`` that of its ``repro.repartition`` span (the real one).
Left out where a step has no such spans or arguments."""
import sys

from chipbench.spans import for_run


def call_arg(spans, name: str, arg: str, call) -> int | None:
    """Argument ``arg`` of the first span named ``name`` inside
    ``call``'s span, as an int; None where there is none."""
    for s in spans.named(name):
        if call["start_ns"] <= s.start and s.end <= call["end_ns"]:
            return int(s.args[arg]) if arg in s.args else None
    return None


def read(run):
    spans = for_run(run) if run.calls else None
    if spans is None:
        return None
    pairs = [(call_arg(spans, "repro.solve", "slots", c),
              call_arg(spans, "repro.repartition", "n", c))
             for c in run.calls]
    if any(None in p for p in pairs):
        print("pad_share: a step has no repro.solve slots or "
              "repro.repartition n", file=sys.stderr)
        return None
    slots = sum(s for s, _ in pairs)
    print(f"pad_share: (slots, n) per step {pairs}", file=sys.stderr)
    return 100.0 * (slots - sum(n for _, n in pairs)) / slots
