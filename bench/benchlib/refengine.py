"""Plain reference of the simulator's semantics, in ordinary Python.

This is the yardstick that decides ``correct``.  It imports nothing of
the simulator under test: it re-states, one component and one message at
a time, the published rules of the event-driven engine with Smart
Ticking (the Akita paper, section 3.2):

* an epoch jumps virtual time to the earliest wake time of any
  component or connection;
* connections deliver first: each active connection arbitrates its
  members round-robin, one message per destination port, into the
  destination's incoming buffer if it has room; the message becomes
  ready at ``t + latency``;
* components whose wake time has come tick, kind by kind;
* a tick that made progress wakes its component again on the next grid
  point of its clock, a tick that asks for a time wakes it then, and an
  idle one sleeps; an arrival wakes its destination at its ready time,
  a freed outgoing buffer wakes its owner, and a freed incoming buffer
  or a new send wakes the serving connection (availability
  backpropagation);
* the run ends when no event remains before the horizon.

Virtual time is kept in the precision the configuration states: every
time computation rounds through ``Num.f``, which is float32 for the
reference and bfloat16 for the control (``Num("bfloat16")``).  Python
floats carry the values between roundings; a sum or product of two
values in the narrow type is exact in a double, so one rounding after
each operation gives the narrow type's own result.
"""
from __future__ import annotations

import collections
import math

import ml_dtypes
import numpy as np

INF = math.inf

# message words: opcode, source port, destination port, ready time,
# then four payload words
OP, SRC, DST, TIME, P0, P1 = 0, 1, 2, 3, 4, 5


class Num:
    """Rounding of time arithmetic to one floating-point type."""

    def __init__(self, dtype: str = "float32"):
        self.dtype = dtype
        t = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}[dtype]
        self.f = lambda x: float(t(x))
        self.eps = self.f(1e-3)

    def after(self, t, period):
        """First grid point of ``period`` strictly after ``t``."""
        if t == INF:
            return INF
        f = self.f
        return f(f(math.floor(f(f(t / period) + self.eps)) + 1.0) * period)

    def at_or_after(self, t, period):
        """First grid point of ``period`` at or after ``t``."""
        if t == INF:
            return INF
        f = self.f
        return f(math.ceil(f(f(t / period) - self.eps)) * period)


def wrap32(x: int) -> int:
    """Two's-complement 32-bit wraparound of a Python int."""
    return ((x + (1 << 31)) & 0xFFFFFFFF) - (1 << 31)


class Port:
    __slots__ = ("gid", "owner", "cap", "inq", "outq", "conn", "peer",
                 "period")

    def __init__(self, gid, owner, cap, period):
        self.gid, self.owner, self.cap = gid, owner, cap
        self.period = period
        self.inq = collections.deque()
        self.outq = collections.deque()
        self.conn = -1
        self.peer = -1


class View:
    """One component's ports during its tick, at time ``t``."""

    __slots__ = ("ports", "t_eps")

    def __init__(self, ports, t_eps):
        self.ports, self.t_eps = ports, t_eps

    def peek(self, p):
        q = self.ports[p].inq
        if q and q[0][TIME] <= self.t_eps:
            return q[0], True
        return None, False

    def recv(self, p, when=True):
        msg, ok = self.peek(p)
        if ok and when:
            self.ports[p].inq.popleft()
            return msg, True
        return msg, False

    def can_send(self, p):
        port = self.ports[p]
        return len(port.outq) < port.cap

    def send(self, p, msg, when=True):
        """``msg`` is a list of words; a destination below 0 means the
        port's default peer."""
        port = self.ports[p]
        if not (when and len(port.outq) < port.cap):
            return False
        msg = list(msg)
        msg[SRC] = port.gid
        if msg[DST] < 0:
            msg[DST] = port.peer
        port.outq.append(msg)
        return True


def new_msg(op, dst=-1, p0=0, p1=0):
    return [op, -1, dst, 0.0, p0, p1, 0, 0]


def reply(msg, op, p0=0, p1=0):
    return new_msg(op, dst=msg[SRC], p0=p0, p1=p1)


class Kind:
    """Instances of one component kind: a tick function, per-instance
    state dicts, ports per instance, buffer capacity and clock period."""

    def __init__(self, name, tick, states, n_ports, cap, period=1.0):
        self.name, self.tick, self.states = name, tick, states
        self.n, self.n_ports, self.cap = len(states), n_ports, cap
        self.period = period


class RefSim:
    """A built topology: kinds in order, then connections."""

    def __init__(self, kinds, num: Num | None = None):
        self.num = num or Num()
        self.kinds = kinds
        self.ports: list[Port] = []
        self.comps = []          # (kind, instance, [ports])
        self.base = {}
        for k in kinds:
            self.base[k.name] = len(self.ports)
            for i in range(k.n):
                cid = len(self.comps)
                ps = []
                for _ in range(k.n_ports):
                    p = Port(len(self.ports), cid, k.cap, k.period)
                    self.ports.append(p)
                    ps.append(p)
                self.comps.append((k, i, ps))
        self.conns: list[list[int]] = []
        self.latency: list[float] = []

    def port(self, kind, inst, p):
        k = next(k for k in self.kinds if k.name == kind)
        return self.base[kind] + inst * k.n_ports + p

    def connect(self, members, latency):
        c = len(self.conns)
        for g in members:
            assert self.ports[g].conn == -1
            self.ports[g].conn = c
        if len(members) == 2:
            a, b = members
            self.ports[a].peer, self.ports[b].peer = b, a
        self.conns.append(list(members))
        self.latency.append(self.num.f(latency))
        return c

    # ------------------------------------------------------------------
    def run(self, until, max_epochs=2_000_000, params=None):
        """Run to ``until`` (or the epoch budget); returns the engine
        counters.  ``params`` maps kind name to its model parameters."""
        num, f = self.num, self.num.f
        params = params or {}
        n_comp = len(self.comps)
        nt = [0.0] * n_comp                       # per-component wake
        cw = [INF] * len(self.conns)              # per-connection wake
        rr = [0] * len(self.conns)
        m_all = max(len(m) for m in self.conns)   # arbitration modulus
        ports = self.ports
        stats = {"epochs": 0, "ticks": 0, "progress_ticks": 0,
                 "delivered": 0}
        t = 0.0
        horizon = f(f(until) + num.eps)
        while stats["epochs"] < max_epochs:
            t_next = min(min(nt), min(cw))
            if not t_next <= horizon:
                break
            t = t_next
            t_eps = f(t + num.eps)
            wake1 = num.after(t, 1.0)
            wake_comp = {}

            def wake(cid, w):
                if w < wake_comp.get(cid, INF):
                    wake_comp[cid] = w

            # --- delivery ----------------------------------------------
            for c, members in enumerate(self.conns):
                if not cw[c] <= t_eps:
                    continue
                best = {}
                for m, g in enumerate(members):
                    q = ports[g].outq
                    if not q:
                        continue
                    dst = q[0][DST]
                    if dst < 0 or dst >= len(ports):
                        continue
                    if len(ports[dst].inq) >= ports[dst].cap:
                        continue
                    prio = (m - rr[c]) % m_all
                    if dst not in best or prio < best[dst][0]:
                        best[dst] = (prio, m, g)
                arrive = f(t + self.latency[c])
                for dst, (prio, m, g) in best.items():
                    src = ports[g]
                    full = len(src.outq) == src.cap
                    msg = src.outq.popleft()
                    msg[TIME] = arrive
                    ports[dst].inq.append(msg)
                    wake(ports[dst].owner,
                         num.at_or_after(arrive, ports[dst].period))
                    if full:
                        wake(src.owner, num.after(t, src.period))
                stats["delivered"] += len(best)
                if best:
                    last = max(best.values())[1]
                    rr[c] = (last + 1) % m_all
                    pending = any(ports[g].outq for g in members)
                    cw[c] = wake1 if pending else INF
                else:
                    cw[c] = INF

            # --- ticks -------------------------------------------------
            asked = set()
            for cid, (kind, i, ps) in enumerate(self.comps):
                if not nt[cid] <= t_eps:
                    continue
                ic0 = [len(p.inq) for p in ps]
                oc0 = [len(p.outq) for p in ps]
                progress, nxt = kind.tick(kind.states[i], View(ps, t_eps),
                                          t, params.get(kind.name), num)
                stats["ticks"] += 1
                stats["progress_ticks"] += bool(progress)
                w = num.after(t, kind.period) if progress else INF
                if nxt > -0.5:
                    w = max(f(nxt), t_eps)
                ready = [p.inq[0][TIME] for p in ps
                         if p.inq and p.inq[0][TIME] > t_eps]
                if ready:
                    w = min(w, num.at_or_after(min(ready), kind.period))
                nt[cid] = w
                for p, a, b in zip(ps, ic0, oc0):
                    if (a == p.cap and len(p.inq) < p.cap) \
                            or len(p.outq) > b:
                        asked.add(p.conn)
            for cid, w in wake_comp.items():
                nt[cid] = min(nt[cid], w)
            for c in asked:
                if c >= 0:
                    cw[c] = min(cw[c], wake1)
            stats["epochs"] += 1
        stats["virtual_time"] = t
        return stats
