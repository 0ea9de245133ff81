"""Merge rounds the int8 ADC kernel ran, as a share of the K rounds per
tile a fixed extract-min merge would run, in %: the sum of the `filter`
spans' `merge_rounds` over the sum of their `merge_rounds_full`.  None
where no filter span carries the count (a program without it, or the
XLA path)."""


def read(ctx):
    counted = [s["attrs"] for s in ctx.spans
               if s["name"] == "filter" and "merge_rounds" in s["attrs"]]
    full = sum(a["merge_rounds_full"] for a in counted)
    if not full:
        return None
    return 100.0 * sum(a["merge_rounds"] for a in counted) / full
