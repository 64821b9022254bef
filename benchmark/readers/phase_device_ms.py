"""Device busy time inside one harness annotation (`bench:<phase>`), in
milliseconds per run of the annotation (per apply, for `apply`)."""


def read(context, phase):
    trace = context["trace"]
    if not trace or not trace["devices"]:
        return None
    entry = trace["phases"].get(phase)
    if not entry or not entry["count"]:
        return None
    return 1e3 * entry["device_busy_s"] / entry["count"]
