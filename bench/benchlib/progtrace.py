"""Reduction of a traced slice to the program's own spans and scopes.

The program under test writes its phases to the profiler itself:

* host spans (``repro.obs.bus.Bus.span``, a ``TraceAnnotation`` under
  the plain name) around the phases of a campaign (``sweep``,
  ``sweep.build``, ``sweep.params``, ``sweep.rounds``,
  ``sweep.transfer``, ``sweep.extract``), of its round loop
  (``round.assemble``, ``round.launch``, ``round.wait``,
  ``round.harvest``, ``rounds.final``) and of one simulation
  (``engine.init_state``, ``engine.run``).  They nest;
* ``jax.named_scope`` blocks on the phases of the engine's epoch
  (``engine.next_event``, ``engine.deliver``, ``engine.tick.<kind>``,
  ``engine.update``), which reach the device ops as op-name metadata.

This module reads the ``.xplane.pb`` a traced run leaves under
``.bench_trace/<cell>`` (the harness's path) once per file and reduces
it to:

* idle: the stretches of the traced window in which no operation ran on
  the device (as ``benchlib.trace`` finds them), split by the
  **innermost** program span open at each moment, by the harness span
  around it, and by whether a program was running (``in_program``) or
  not (``between_programs``);
* self time per program span (its time less that of the spans inside
  it), and how many of each closed inside the window;
* launches: the ``XLA Modules`` events that start in the window;
* scopes: device busy time by engine scope.  The op-name metadata comes
  from xprof's ``op_profile`` of the same file (each op's provenance;
  a fusion's from its fused ops); the time from the same innermost op
  events, clipped to the window, that ``benchlib.trace`` counts as busy.
  A fusion whose fused ops all lie in one scope counts to it, one whose
  fused ops lie in several to ``mixed``, and an op outside every scope
  to ``unscoped``.  Fused ops outside every scope, and those that only
  move a value (constants, parameters, broadcasts, bitcasts, reshapes:
  XLA hoists them out of the scopes and shares one among them), do not
  decide a fusion's scope.  ``op_profile`` writes
  ``ALL_HOSTS.op_stats.pb`` beside the file it reads.

A span still open when the profiler stops is not in the trace, and a
program without these spans or scopes (an older checkout) reads as
having none: the readers then return ``None``.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re

from benchlib import harness
from benchlib.trace import (MODULES_LINE, OPS_LINE, Lookup, clip, find_xplane,
                            gaps, leaves, merge)

PROGRAM_SPANS = ("sweep", "sweep.build", "sweep.params", "sweep.rounds",
                 "sweep.transfer", "sweep.extract", "round.assemble",
                 "round.launch", "round.wait", "round.harvest",
                 "rounds.final", "engine.init_state", "engine.run")
HARNESS_SPANS = ("run_sweep", "state.build", "sim.run", "extract")
MARKS = ("trace.begin", "trace.end")
SCOPE = "engine."
MIXED, UNSCOPED = "mixed", "unscoped"
# fused ops that only move a value: XLA hoists them out of the scopes or
# shares one among several, so their provenance names no phase's work
NO_WORK = ("constant", "parameter", "broadcast", "bitcast", "reshape")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def logdir(run) -> str:
    return os.path.join(harness.ROOT, ".bench_trace", run.cell.name)


def innermost(spans):
    """Disjoint ``(start, end, name)`` pieces of nested ``(start, end,
    name)`` spans: at each moment the span that opened last among those
    still open (on a tie, the shorter)."""
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    out, open_, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(order) and order[k][0] <= a:
            open_.append(order[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        if open_:
            top = max(open_, key=lambda sp: (sp[0], -sp[1]))
            if out and out[-1][1] == a and out[-1][2] == top[2]:
                out[-1] = (out[-1][0], b, top[2])
            else:
                out.append((a, b, top[2]))
    return out


def window(host_spans):
    """The traced window, as ``benchlib.trace.reduce_events`` finds it."""
    marks = {n: s for s, _, n in host_spans if n in MARKS}
    if len(marks) == 2:
        return marks["trace.begin"], marks["trace.end"]
    for s, e, n in host_spans:
        if n == "window":
            return s, e
    raise ValueError("the trace holds neither a 'window' span nor "
                     "'trace.begin' and 'trace.end' marks")


def reduce_spans(host_spans, devices):
    """The span reduction on plain data.

    ``host_spans``: ``(start_ns, end_ns, name)`` of the program's spans,
    the harness's spans and marks; ``devices``: per device a dict with
    ``ops`` (innermost ``(start_ns, end_ns, name)``) and ``modules``
    (``(start_ns, end_ns, name)``).  Returns ``window_s``, ``idle`` (a
    dict ``(harness span, innermost program span, in_program |
    between_programs) -> s``, ``None`` where no such span was open),
    ``self_s`` and ``count`` per program span, and ``launches``; device
    quantities are averaged over the devices."""
    lo, hi = window(host_spans)
    inside = [(s, e, n) for s, e, n in host_spans
              if s >= lo and e <= hi]
    prog = Lookup(innermost([sp for sp in inside
                             if sp[2] in PROGRAM_SPANS]))
    outer = Lookup([sp for sp in inside if sp[2] in HARNESS_SPANS])
    self_s, count = {}, {}
    for s, e, n in prog.iv:
        self_s[n] = self_s.get(n, 0.0) + (e - s) / 1e9
    for _, _, n in inside:
        if n in PROGRAM_SPANS:
            count[n] = count.get(n, 0) + 1
    idle, launches = {}, 0
    for dev in devices:
        busy = merge(clip([(s, e) for s, e, _ in dev["ops"]], lo, hi))
        mods = Lookup([(s, e, "in_program") for s, e in
                       merge(clip([m[:2] for m in dev["modules"]], lo, hi))])
        launches += sum(lo <= s <= hi for s, _, _ in dev["modules"])
        cuts = sorted({t for iv in (prog.iv, outer.iv, mods.iv)
                       for s, e, _ in iv for t in (s, e)})
        for s, e in gaps(busy, lo, hi):
            i = bisect.bisect_right(cuts, s)
            j = bisect.bisect_left(cuts, e)
            edges = [s] + cuts[i:j] + [e]
            for a, b in zip(edges, edges[1:]):
                mid = 0.5 * (a + b)
                key = (outer.at(mid), prog.at(mid),
                       mods.at(mid) or "between_programs")
                idle[key] = idle.get(key, 0.0) + (b - a) / 1e9
    n = max(1, len(devices))
    return {"window_s": (hi - lo) / 1e9,
            "idle": {k: v / n for k, v in idle.items()},
            "self_s": self_s, "count": count, "launches": launches / n}


def scope_of(provenance: str) -> str:
    """The innermost engine scope of an op's provenance (its op-name
    path, such as ``jit(_run)/while/body/engine.deliver/eq:``), or
    ``unscoped``."""
    found = [p for p in provenance.split("/") if p.startswith(SCOPE)]
    return found[-1].rstrip(":") if found else UNSCOPED


def _opcode(node) -> str | None:
    expr = node.get("xla", {}).get("expression", "")
    m = _OPCODE.search(expr.split(" = ", 1)[-1])
    return m.group(1) if m else None


def op_scope(op: dict) -> str:
    """The scope of one op of ``op_profile``: its own provenance's, or
    for a fusion the one scope its fused ops lie in (``mixed`` where
    they lie in several), counting only those that do work in a
    scope."""
    fused = op.get("children") or []
    if not fused:
        return scope_of(op.get("xla", {}).get("provenance", ""))
    scopes = {scope_of(f.get("xla", {}).get("provenance", ""))
              for f in fused if _opcode(f) not in NO_WORK} - {UNSCOPED}
    if len(scopes) == 1:
        return scopes.pop()
    return MIXED if scopes else UNSCOPED


def scope_map(profile: dict) -> dict:
    """``{program: {op name: scope}}`` from an ``op_profile`` tree:
    programs under ``byProgram``, then categories, then ops or groups
    of an op and its duplicates, and under an op its fused ops."""
    out = {}

    def walk(node, ops):
        if node.get("xla", {}).get("expression") and \
                not node["name"].endswith(" and its duplicate(s)"):
            ops[node["name"]] = op_scope(node)
            return
        for child in node.get("children") or []:
            walk(child, ops)

    for program in profile["byProgram"].get("children") or []:
        walk(program, out.setdefault(program["name"], {}))
    return out


def scope_busy(devices, lo, hi, scopes: dict) -> dict:
    """Device busy seconds per scope, averaged over the devices: each
    innermost op, clipped to the window, counts to the scope of its
    name in the program whose ``XLA Modules`` event holds it."""
    out = {}
    for dev in devices:
        mods = Lookup(dev["modules"])
        for s, e, name in dev["ops"]:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            prog = scopes.get(mods.at(0.5 * (s + e)), {})
            key = prog.get(name, UNSCOPED)
            out[key] = out.get(key, 0.0) + (e - s) / 1e9
    n = max(1, len(devices))
    return {k: v / n for k, v in out.items()}


def _name(hlo: str) -> str:
    """``%fusion.6 = s32[4225]... fusion(...)`` -> ``fusion.6``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


@functools.lru_cache(maxsize=2)
def _parse(path: str, stamp):
    from jax.profiler import ProfileData
    wanted = set(PROGRAM_SPANS + HARNESS_SPANS + MARKS + ("window",))
    host_spans, devices = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
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
                         _name(ev.name)) for ev in line.events)
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name)
                                      for ev in line.events]
            if dev["ops"]:
                devices.append(dev)
    return host_spans, devices


def load(path: str):
    """``(host_spans, devices)`` of one ``.xplane.pb``, parsed once."""
    st = os.stat(path)
    return _parse(path, (st.st_mtime_ns, st.st_size))


@functools.lru_cache(maxsize=2)
def _profile(path: str, stamp):
    from xprof.convert import raw_to_tool_data
    data = raw_to_tool_data.xspace_to_tool_data([path], "op_profile", {})[0]
    return json.loads(data)


def summarize(path: str) -> dict:
    """The span reduction of one ``.xplane.pb``."""
    host_spans, devices = load(path)
    return reduce_spans(host_spans, devices)


def scopes(path: str) -> dict | None:
    """Device busy seconds per engine scope (plus ``mixed`` and
    ``unscoped``) in the window of one ``.xplane.pb``; ``None`` where
    xprof is not installed or no op carries an engine scope."""
    try:
        import xprof  # noqa: F401
    except ImportError:
        return None
    host_spans, devices = load(path)
    lo, hi = window(host_spans)
    st = os.stat(path)
    table = scope_map(_profile(path, (st.st_mtime_ns, st.st_size)))
    busy = scope_busy(devices, lo, hi, table)
    if not any(k.startswith(SCOPE) for k in busy):
        return None
    return busy


def _xplane(run) -> str | None:
    if not run.trace:
        return None
    try:
        return find_xplane(logdir(run))
    except FileNotFoundError:
        return None


def idle_share(run, names) -> float | None:
    """Per cent of the traced window in which the device was idle while
    the innermost program span was one of ``names``; ``None`` where no
    such span closed inside the window."""
    path = _xplane(run)
    if path is None:
        return None
    red = summarize(path)
    if not any(red["count"].get(n) for n in names) or red["window_s"] <= 0:
        return None
    idle = sum(v for (_, span, _), v in red["idle"].items() if span in names)
    return 100.0 * idle / red["window_s"]


def launches_per_point(run) -> float | None:
    """``XLA Modules`` launches in the traced window over the design
    points of the campaigns (``sweep`` spans) that closed inside it."""
    path = _xplane(run)
    if path is None:
        return None
    red = summarize(path)
    points = red["count"].get("sweep", 0) * run.cell.traffic["points"]
    return red["launches"] / points if points else None


def busy_share(run, prefix: str) -> float | None:
    """Per cent of the device busy time in the traced window that ops of
    the engine scopes starting with ``prefix`` took."""
    path = _xplane(run)
    if path is None:
        return None
    busy = scopes(path)
    if not busy:
        return None
    total = sum(busy.values())
    part = sum(v for k, v in busy.items()
               if k == prefix or k.startswith(prefix + "."))
    return 100.0 * part / total if total > 0 else None
