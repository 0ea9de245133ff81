"""Mean real query rows per micro-batcher flush (the `n_real` of the
batch-level `flush` spans)."""


def read(ctx):
    rows = [s["attrs"]["n_real"] for s in ctx.spans
            if s["name"] == "flush" and "bucket" in s["attrs"]]
    return sum(rows) / len(rows) if rows else None
