"""The device's idle share of the traced window, in percent: 1 minus the
union of the device-operation intervals over the window."""


def read(spec: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
