"""Roofline arithmetic shared by the per-layer readers.

A kernel's share of its roofline is the least time the chip could take
for the work its stage needs, the larger of operations over the peak
rate and bytes over the memory bandwidth, divided by the device time
the trace gives the stage's program.  The work is counted from shapes by
each reader's own function, never from the program.

On a cell of several chips (a sharded placement) the trace's device time
of a program is summed over the chips (`devtrace.program`).  A reader
then counts the work of the whole corpus against one chip's peak: the
whole work over the summed time of all chips is the same share as each
chip's part of the work over its own time.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peak row of a device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(have {sorted(table)})")
    return table[device_kind]


def least_time(ops: float, nbytes: float, ops_per_s: float,
               bytes_per_s: float) -> tuple[float, str]:
    """(seconds, 'compute' or 'memory'): the bound that applies."""
    t_ops, t_bytes = ops / ops_per_s, nbytes / bytes_per_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def share(ctx, module_prefix: str, work) -> float | None:
    """Percent of the roofline reached by the program `module_prefix`
    over the traced window.  `work(ctx)` gives (ops, bytes, peak key) of
    one execution.  None when the trace holds no execution."""
    if ctx.trace is None:
        return None
    from . import devtrace
    prog = devtrace.program(ctx.trace, module_prefix)
    if prog["calls"] == 0 or prog["busy_s"] <= 0:
        return None
    ops, nbytes, peak_key = work(ctx)
    p = peaks(ctx.device_kind)
    t, _ = least_time(ops, nbytes, p[peak_key], p["hbm_bytes_per_s"])
    return 100.0 * prog["calls"] * t / prog["busy_s"]
