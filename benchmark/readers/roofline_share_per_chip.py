"""`roofline_share` for a cell that runs one program across several
chips: the trace's device times are averaged over the device planes, so
the work they are held against is one chip's share of the fit's,
`benchmark/costs/<cost>.py`'s count over `trace["devices"]`. On one
chip it reads what `roofline_share` reads."""

from . import roofline_share


def read(context, cost, kind, pattern, phase):
    share = roofline_share.read(context, cost, kind, pattern, phase)
    if share is None:
        return None
    context["notes"][cost]["chips"] = context["trace"]["devices"]
    return share / context["trace"]["devices"]
