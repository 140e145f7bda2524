"""A device program's share of the memory roofline, in percent.

The work is counted from the semantics, not from what implements them:
the plain reference walks this run's sample of requests and counts the
tuple rows it has to look at (edges followed and membership probes) per
answer; ``bytes_per_row`` bytes each (stated in the metric's file); times
the answers per second of the window; over the chip's peak bytes per
second: the least device seconds a second of this traffic needs.  Over
the device seconds that the matching modules took per second of the
traced window.  It is bound by memory: there is no matrix work."""

from readers import trace_module_time


def read(spec: dict, ctx: dict):
    trace, peak = ctx["trace"], ctx["peak"]
    if trace is None:
        return None
    if peak is None:
        raise KeyError(
            f"device {ctx['device']['kind']!r} is not in peaks.json")
    seconds, runs = trace_module_time.modules(spec, ctx)
    if not runs or trace["window_s"] <= 0:
        return None
    bytes_per_s = (ctx["rows_per_unit"] * float(spec["bytes_per_row"])
                   * ctx["units_per_s"])
    least_s = bytes_per_s / float(peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (seconds / trace["window_s"])
