"""A counter of the program, by its delta over one phase of the window
(or over all phases), divided by a count the mode reports."""


def read(context, counter, per, phase=None):
    by_phase = context["counters"]
    phases = [phase] if phase else list(by_phase)
    delta = sum(by_phase.get(p, {}).get(counter, 0.0) for p in phases)
    n = context["stats"].get(per)
    if not n:
        return None
    return delta / n
