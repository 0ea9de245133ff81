"""Share of its roofline reached by the flat float32 filter program
(`l2_topk.ops.knn`: the Pallas distance tiles and the top-k merge), in %.

Work of one execution at nq query rows over the n stored rows: every
ciphertext read once (n * d * 4 bytes) and 2 * nq * n * d operations,
counted against the bf16 peak since the chip has no faster float32 path.
"""

PROGRAM = "jit_knn"


def work(ctx):
    n, d, nq = ctx.n_rows, ctx.d, ctx.rows_per_call
    return 2.0 * nq * n * d, 4.0 * n * d, "bf16_flops_per_s"


def read(ctx):
    from bench.roofline import share
    return share(ctx, PROGRAM, work)
