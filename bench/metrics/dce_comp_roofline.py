"""Share of its roofline reached by the DCE refine program
(`search_engine.refine_candidates`: candidate gather, Pallas Z tiles,
win counts, selection), in %.

Work of one execution at nq query rows with k' candidates each: every
candidate's DCE ciphertext read once (4 vectors of 2d+16 float32), and
the fewest comparisons an exact top-10 selection from k' needs,
(k'-1) + 9 * ceil(log2 k'), at 4 * (2d+16) operations each, counted
against the bf16 peak.
"""

import math

PROGRAM = "jit_refine_candidates"


def work(ctx):
    nq, kp, k = ctx.rows_per_call, ctx.kp, ctx.k
    width = 2 * ctx.d + 16
    comparisons = (kp - 1) + (k - 1) * math.ceil(math.log2(kp))
    return (nq * comparisons * 4.0 * width, nq * kp * 4.0 * width * 4.0,
            "bf16_flops_per_s")


def read(ctx):
    from bench.roofline import share
    return share(ctx, PROGRAM, work)
