"""Reduction of a `jax.profiler` trace to device metrics.

A trace is reduced to a flat list of events, each a dict with `plane`,
`line`, `name`, `start_ns` and `dur_ns` (and `module` for device ops:
the program the op ran in), so that the arithmetic below can be checked
on a small recorded trace without JAX.

  busy      the union of the intervals in which an op ran on a device
  idle      1 - busy / traced window, averaged over the devices used;
            the window runs from the first recorded device op to the
            end of the last (`observed`)
  program   the busy time, and the number of executions, of the jitted
            programs whose module name starts with a given prefix
  breakdown the device ops that took most time, and the longest idle
            gaps with what the host was doing in each
"""

from __future__ import annotations

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "bench_clock_sync"
_MODULE_ID = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def module_name(event_name: str) -> str:
    """'jit_knn(123)' -> 'jit_knn'."""
    return _MODULE_ID.sub("", event_name)


def load_events(path: str) -> list[dict]:
    """Events of the TPU planes and the host's `SYNC_EVENT`, from an
    `.xplane.pb` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        is_device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            keep_line = is_device and line.name in (OPS_LINE, MODULES_LINE)
            for ev in line.events:
                if not (keep_line or ev.name == SYNC_EVENT):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return attach_modules(out)


def attach_modules(events: list[dict]) -> list[dict]:
    """Give each device op the module (program) whose execution on the
    same device contains its start."""
    mods = collections.defaultdict(list)
    for e in events:
        if e["line"] == MODULES_LINE:
            mods[e["plane"]].append((e["start_ns"],
                                     e["start_ns"] + e["dur_ns"],
                                     module_name(e["name"])))
    starts = {}
    for plane, v in mods.items():
        v.sort()
        starts[plane] = [lo for lo, _, _ in v]
    for e in events:
        if e["line"] != OPS_LINE:
            continue
        e["module"] = ""
        v = mods.get(e["plane"])
        if not v:
            continue
        j = bisect.bisect_right(starts[e["plane"]], e["start_ns"]) - 1
        if j >= 0 and e["start_ns"] < v[j][1]:
            e["module"] = v[j][2]
    return events


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def _ops(events, plane=None, module_prefix=None):
    for e in events:
        if e["line"] != OPS_LINE:
            continue
        if plane is not None and e["plane"] != plane:
            continue
        if module_prefix is not None and \
                not e.get("module", "").startswith(module_prefix):
            continue
        yield e


def devices(events) -> list[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def busy_intervals(events, plane: str, module_prefix=None):
    return union((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in _ops(events, plane, module_prefix))


def busy_ns(events, plane: str, module_prefix=None) -> float:
    return sum(hi - lo for lo, hi in
               busy_intervals(events, plane, module_prefix))


def clip(events, lo: float, hi: float) -> list[dict]:
    """The events cut to the window [lo, hi] (ns); those outside go."""
    out = []
    for e in events:
        a, b = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
        if b > a or (e["dur_ns"] == 0 and lo <= a <= hi):
            out.append(dict(e, start_ns=a, dur_ns=max(0.0, b - a)))
    return out


def observed(events, window: tuple[float, float]) -> tuple[float, float]:
    """The part of `window` (lo_ns, hi_ns) from the start of the first
    device op the profiler recorded to the end of the last.  An op that
    was already running when the profiler started is not recorded at
    all, so the device's state before the first recorded op is unknown:
    counting that stretch as idle would read a long kernel as an idle
    device."""
    ops = list(_ops(events))
    if not ops:
        return window
    lo = max(window[0], min(e["start_ns"] for e in ops))
    hi = min(window[1], max(e["start_ns"] + e["dur_ns"] for e in ops))
    return (lo, hi) if hi > lo else window


def summary(events, window: tuple[float, float]) -> dict:
    """busy_s (mean over devices), window_s and idle share of events
    already clipped to `window` (lo_ns, hi_ns)."""
    window_ns = window[1] - window[0]
    planes = devices(events)
    if not planes or window_ns <= 0:
        return {"busy_s": 0.0, "window_s": window_ns / 1e9, "idle": None}
    busy = sum(busy_ns(events, p) for p in planes) / len(planes)
    return {"busy_s": busy / 1e9, "window_s": window_ns / 1e9,
            "idle": 1.0 - busy / window_ns}


def program(events, module_prefix: str) -> dict:
    """Busy seconds of the programs whose module name starts with
    `module_prefix` (summed over devices) and their execution count (on
    the first device)."""
    planes = devices(events)
    calls = sum(1 for e in events
                if e["line"] == MODULES_LINE and planes
                and e["plane"] == planes[0]
                and module_name(e["name"]).startswith(module_prefix))
    busy = sum(busy_ns(events, p, module_prefix) for p in planes)
    return {"busy_s": busy / 1e9, "calls": calls}


def op_name(hlo: str) -> str:
    """'%sort.7 = (f32[32,4256]{...}, ...) sort(...)' -> '%sort.7 sort'."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}" if m else head


def top_ops(events, n: int = 10) -> list[list]:
    """[[module/op, seconds], ...] of the ops with the most device time."""
    tot = collections.Counter()
    for e in _ops(events):
        tot[f"{e.get('module', '')}/{op_name(e['name'])}"] += e["dur_ns"]
    return [[name, ns / 1e9] for name, ns in tot.most_common(n)]


def idle_gaps(events, window: tuple[float, float], label, n: int = 10):
    """The `n` longest gaps between busy intervals of the first device
    inside `window` (start_ns, end_ns), as [[label(mid_ns), seconds]].
    `label` names what the host was doing at a trace time."""
    planes = devices(events)
    if not planes:
        return []
    lo, hi = window
    busy = [(max(a, lo), min(b, hi)) for a, b in
            busy_intervals(events, planes[0]) if b > lo and a < hi]
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label((a + b) / 2), (b - a) / 1e9] for a, b in gaps[:n]]


def sync_offset_ns(events, t_sync_s: float) -> float | None:
    """trace_ns - monotonic_s * 1e9, from the `SYNC_EVENT` annotation
    opened at monotonic time `t_sync_s`; None when it is not there."""
    for e in events:
        if e["name"] == SYNC_EVENT:
            return e["start_ns"] - t_sync_s * 1e9
    return None


def span_label(spans, offset_ns: float | None):
    """A label function for `idle_gaps`: the innermost engine-side
    `repro.obs` span open at that time ('filter', 'refine', 'flush'),
    else 'no batch open'.  Spans are dicts with name/t_start/t_end in
    monotonic seconds."""
    batch = [s for s in spans if s["name"] in ("flush", "filter", "refine")]
    depth = {"flush": 0, "filter": 1, "refine": 1}

    def label(t_ns: float) -> str:
        if offset_ns is None:
            return "unattributed"
        t = (t_ns - offset_ns) / 1e9
        best = None
        for s in batch:
            if s["t_start"] <= t < s["t_end"] and (
                    best is None or depth[s["name"]] > depth[best["name"]]):
                best = s
        return "host: no batch open" if best is None \
            else f"host: {best['name']}"

    return label
