"""A profiler trace of a few seconds inside the window, and its reduction.

``capture`` records with JAX's profiler (no Python tracer, so the host cost
is the runtime's own trace points) and marks the traced window with a
``chipbench_window`` annotation. ``load_events`` flattens the ``.xplane.pb``
into plain events, and ``summarize`` reduces them:

- busy: the union of the intervals in which an operation ran on a device
  (its ``XLA Ops`` line), inside the window, averaged over the devices;
- the device operations that took the most time, by self time (a loop's
  time less its body's), named ``<program>:<op>`` after the program
  (``XLA Modules``) that ran them;
- the longest idle gaps, each named by the host event that overlaps it
  most (what the host was doing while the device waited).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time

WINDOW = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_HOST = "no traced host event"


def capture(directory: str, seconds: float) -> str:
    """Trace for ``seconds``; returns the ``.xplane.pb`` written."""
    import jax
    from jax.profiler import ProfileOptions
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return files[0]


def load_events(path: str) -> list:
    """Every event of the trace: dicts with plane, line, name, start and
    end in nanoseconds."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start": float(e.start_ns),
                            "end": float(e.start_ns) + float(e.duration_ns)})
    return out


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``;
    ``jit_f(123)`` -> ``jit_f``."""
    name = hlo.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\(\d+\)$", "", name)


def self_times(ops, lo: float, hi: float):
    """(event, self time) of each operation clipped to [lo, hi): its time
    less the time of the operations nested in it (a loop holds its body's
    operations), so that the times add up to the busy time once."""
    spans = sorted(((max(e["start"], lo), min(e["end"], hi), e) for e in ops
                    if min(e["end"], hi) > max(e["start"], lo)),
                   key=lambda t: (t[0], -t[1]))
    own = [b - a for a, b, _ in spans]
    stack = []
    for i, (a, b, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, spans[stack[-1]][1]) - a
        stack.append(i)
    return [(e, max(o, 0.0)) for (_, _, e), o in zip(spans, own)]


def _window(events) -> tuple:
    marks = [e for e in events if e["name"] == WINDOW]
    if marks:
        return marks[0]["start"], marks[0]["end"]
    return (min(e["start"] for e in events), max(e["end"] for e in events))


def summarize(events, top: int = 10) -> dict:
    """busy_s, window_s, device_ops and idle_gaps (see the module doc).
    ``busy_s`` is None when no device operation ran in the window."""
    lo, hi = _window(events)
    devices = sorted({e["plane"] for e in events
                      if e["plane"].startswith("/device:")
                      and e["line"] == OPS_LINE})
    busy_total, per_op, gaps = 0.0, {}, []
    host = [e for e in events if e["plane"].startswith("/host:")
            and e["name"] != WINDOW]
    for dev in devices:
        ops = [e for e in events if e["plane"] == dev
               and e["line"] == OPS_LINE]
        mods = sorted((e["start"], e["end"], short_name(e["name"]))
                      for e in events if e["plane"] == dev
                      and e["line"] == MODULES_LINE)
        starts = [a for a, _, _ in mods]
        busy = clip(union((e["start"], e["end"]) for e in ops), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for e, own in self_times(ops, lo, hi):
            i = bisect.bisect_right(starts, e["start"]) - 1
            prog = mods[i][2] if i >= 0 and e["start"] < mods[i][1] else ""
            key = f"{prog}:{short_name(e['name'])}" if prog \
                else short_name(e["name"])
            per_op[key] = per_op.get(key, 0.0) + own
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not devices or busy_total <= 0:
        return {"busy_s": None, "window_s": (hi - lo) * 1e-9,
                "device_ops": [], "idle_gaps": []}
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        best, overlap = NO_HOST, 0.0
        for e in host:
            o = min(e["end"], b) - max(e["start"], a)
            if o > overlap:
                best, overlap = e["name"], o
        named.append([best, (b - a) * 1e-9])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_total / len(devices) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": named}
