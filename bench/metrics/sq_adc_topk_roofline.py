"""Share of its roofline reached by the int8 ADC filter program
(`adc_topk.ops.sq_knn`: the Pallas code scan with its in-kernel top-k
merge), in %.

Work of one execution at nq query rows over the n stored rows: every
int8 code read once (n * d bytes) and 2 * nq * n * d int8 operations,
counted against the int8 peak.
"""

PROGRAM = "jit_sq_knn"


def work(ctx):
    n, d, nq = ctx.n_rows, ctx.d, ctx.rows_per_call
    return 2.0 * nq * n * d, 1.0 * n * d, "int8_ops_per_s"


def read(ctx):
    from bench.roofline import share
    return share(ctx, PROGRAM, work)
