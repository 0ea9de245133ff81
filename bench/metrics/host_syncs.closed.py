"""Device->host reads per engine call: the mean number of `sync` spans
in the trace of each `batch` root span."""


def read(ctx):
    roots = {s["trace_id"] for s in ctx.spans
             if s["name"] == "batch" and s["parent_id"] is None
             and s["attrs"].get("n_real", 0) > 0}
    if not roots:
        return None
    n = sum(1 for s in ctx.spans
            if s["name"] == "sync" and s["trace_id"] in roots)
    return n / len(roots)
