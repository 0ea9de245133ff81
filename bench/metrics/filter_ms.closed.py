"""Mean host-clock time of the engine's filter stage per engine call, in
ms (the `filter` spans; they end at the host sync on the candidates, so
they include the device time)."""


def read(ctx):
    d = [s["t_end"] - s["t_start"] for s in ctx.spans
         if s["name"] == "filter"]
    return 1e3 * sum(d) / len(d) if d else None
