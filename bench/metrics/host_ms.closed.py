"""Host work per engine call, in ms, during which the chip waits: the
mean over `batch` root spans (one per engine call, under the
micro-batcher or for a direct batch request) of the root's duration
less its `collect` child (the wait for requests to arrive) and less the
`sync` spans of its trace (device->host reads, in which the host waits
on the device).  What is left is batch assembly, dispatch, the host's
share of filter and refine, and delivery."""

WAITS = ("collect", "sync")


def read(ctx):
    roots = {s["trace_id"]: s for s in ctx.spans
             if s["name"] == "batch" and s["parent_id"] is None
             and s["attrs"].get("n_real", 0) > 0}
    if not roots:
        return None
    host = {tid: s["t_end"] - s["t_start"] for tid, s in roots.items()}
    for s in ctx.spans:
        if s["name"] in WAITS and s["trace_id"] in host:
            host[s["trace_id"]] -= s["t_end"] - s["t_start"]
    return 1e3 * sum(host.values()) / len(host)
