"""Share of the traced window in which no op ran on the device, in %
(1 - the union of device-op intervals / the window)."""


def read(ctx):
    if ctx.trace_summary is None or ctx.trace_summary["idle"] is None:
        return None
    return 100.0 * ctx.trace_summary["idle"]
