"""A device program's share of the memory roofline of all the cell's
chips, in percent: ``trace_roofline``'s share over ``device.count``.

The work is counted from the semantics, as there (tuple rows the plain
reference looks at per answer x ``bytes_per_row`` x answers per second),
over the chips' peak bytes per second together: a graph sharded over four
chips has four chips' memory bandwidth to read it with, so the one-chip
reader would overstate the share fourfold.  Over the device seconds the
matching modules took per second of the traced window, averaged over the
planes (``reduce_trace.reduce``).  The collectives between the chips have
no peak in ``peaks.json``: they get a time, not a share."""

from readers import trace_roofline


def read(spec: dict, ctx: dict):
    one_chip = trace_roofline.read(spec, ctx)
    if one_chip is None:
        return None
    return one_chip / int(ctx["device"]["count"])
