"""Mean time a request waits in the scheduler's queue, in ms (the
`queue` spans: admission to the flush that takes it)."""


def read(ctx):
    d = [s["t_end"] - s["t_start"] for s in ctx.spans
         if s["name"] == "queue"]
    return 1e3 * sum(d) / len(d) if d else None
