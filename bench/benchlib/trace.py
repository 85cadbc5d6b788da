"""Reduction of a profiler trace to device busy time, the device ops
that took the most time and the idle gaps by what the host was doing.

The profiler writes one ``.xplane.pb`` file.  Its host plane carries the
harness's own spans (``jax.profiler.TraceAnnotation``) on the Python
thread; each device plane carries the operations that ran on that chip
(line ``XLA Ops``, where control flow such as a ``while`` loop is an
event enclosing the operations of its body) and the programs they belong
to (line ``XLA Modules``).  All are on one clock.

* the traced window: from the harness's ``trace.begin`` mark to its
  ``trace.end`` mark (or its ``window`` span where the whole window was
  traced);
* busy: the union of the intervals of the innermost operations of a
  device, clipped to the traced window, averaged over the devices;
* device ops: the operations with the most device time, by name;
* idle gaps: the stretches of the window in which no operation ran,
  each labelled with the harness span the host was in and with whether
  it fell inside a running program (between the operations of one
  launch, such as the iterations of a while loop) or between programs
  (the host had not yet launched the next one); a stretch that crosses
  a boundary is split there.
"""
from __future__ import annotations

import bisect
import glob
import os

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPANS = ("window", "trace.begin", "trace.end")
TOP = 10


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(files, key=os.path.getmtime)


def leaves(events):
    """The events of a nested line that contain no other event: the
    operations that run, without the control flow (``while``,
    ``conditional``) that encloses them."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    out = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= ev[1]:
            out.append(ev)
    return out


def op_name(hlo: str) -> str:
    """``%fusion.6 = s32[4225]... fusion(...)`` -> ``fusion.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:64]


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi):
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Lookup:
    """Which of a set of disjoint ``(start, end, name)`` intervals holds
    a time."""

    def __init__(self, intervals):
        self.iv = sorted(intervals)
        self.starts = [s for s, _, _ in self.iv]

    def at(self, t):
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.iv[i][1] >= t:
            return self.iv[i][2]
        return None


def reduce_events(host_spans, devices, spans):
    """The reduction on plain data.

    ``host_spans``: ``(start_ns, end_ns, name)`` of the harness's spans;
    ``devices``: per device a dict with ``ops`` (``(start_ns, end_ns,
    name)``) and ``modules`` (``(start_ns, end_ns)``)."""
    marks = {n: s for s, _, n in host_spans
             if n in ("trace.begin", "trace.end")}
    windows = [(s, e) for s, e, n in host_spans if n == "window"]
    if len(marks) == 2:
        lo, hi = marks["trace.begin"], marks["trace.end"]
    elif windows:
        lo, hi = windows[0]
    else:
        raise ValueError("the trace holds neither a 'window' span nor "
                         "'trace.begin' and 'trace.end' marks")
    # inside the window the harness's spans follow one another; a span
    # already open when the profiler started is not in the trace
    labelled = Lookup([(s, e, n) for s, e, n in host_spans
                       if n in spans and n not in WINDOW_SPANS
                       and s >= lo and e <= hi])
    busy_total, op_time, gap_time = 0.0, {}, {}
    for dev in devices:
        ops = clip([(s, e) for s, e, _ in dev["ops"]], lo, hi)
        busy = merge(ops)
        busy_total += sum(e - s for s, e in busy)
        for s, e, name in dev["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_time[name] = op_time.get(name, 0.0) + (e - s)
        mods = Lookup([(s, e, "in_program") for s, e in
                       merge(clip(dev["modules"], lo, hi))])
        cuts = sorted({t for iv in (labelled.iv, mods.iv)
                       for s, e, _ in iv for t in (s, e)})
        for s, e in gaps(busy, lo, hi):
            # a gap that crosses span or program boundaries is split there
            i = bisect.bisect_right(cuts, s)
            j = bisect.bisect_left(cuts, e)
            edges = [s] + cuts[i:j] + [e]
            for a, b in zip(edges, edges[1:]):
                mid = 0.5 * (a + b)
                label = (f"{labelled.at(mid) or 'window'}:"
                         f"{mods.at(mid) or 'between_programs'}")
                gap_time[label] = gap_time.get(label, 0.0) + (b - a)
    n = max(1, len(devices))

    def top(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_total / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top(op_time), "idle_gaps": top(gap_time),
            "devices": len(devices)}


def summarize(logdir: str, spans) -> dict:
    """Read the newest trace under ``logdir`` and reduce it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(logdir))
    host_spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = leaves(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         op_name(ev.name)) for ev in line.events)
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(ev.start_ns,
                                       ev.start_ns + ev.duration_ns)
                                      for ev in line.events]
            if dev["ops"]:
                devices.append(dev)
    if not devices:
        raise ValueError("the trace holds no device operations")
    return reduce_events(host_spans, devices, spans)
