"""The device's idle share, in percent, while the host was inside the
harness annotation ``phase``: 1 minus the union of the device-op
intervals inside the annotation's runs over their summed length. With
no ``phase``, the same over the whole traced window."""


def read(context, phase=None):
    trace = context["trace"]
    if not trace or not trace["devices"]:
        return None
    if phase is None:
        busy, length = trace["busy_s"], trace["window_s"]
    else:
        entry = trace["phases"].get(phase)
        if not entry:
            return None
        busy, length = entry["device_busy_s"], entry["host_s"]
    if length <= 0:
        return None
    return 100.0 * (1.0 - busy / length)
