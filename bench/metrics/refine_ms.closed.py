"""Mean host-clock time of the engine's DCE refine stage per engine
call, in ms (the `refine` spans, which end at the host sync on the
ids)."""


def read(ctx):
    d = [s["t_end"] - s["t_start"] for s in ctx.spans
         if s["name"] == "refine"]
    return 1e3 * sum(d) / len(d) if d else None
