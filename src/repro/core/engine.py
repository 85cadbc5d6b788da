"""The Akita engine in JAX: event-driven core + Smart Ticking (paper §3.2)
+ Availability Backpropagation + transparent vectorized parallelism (§3.3).

Design (see DESIGN.md §3 for the hardware-adaptation rationale):

* Instances of every component kind are rows of batched arrays; one *epoch* of
  a jitted ``lax.while_loop`` advances virtual time straight to the next event
  (``min`` over all wake times) — the event-driven jump that lets Smart
  Ticking skip idle stretches entirely.
* Smart Ticking's four rules (paper §3.2) are vectorized:
    1. message arrival wakes the destination component at the arrival time;
    2. an outgoing buffer going full→not-full wakes its owner;
    3. a tick returning progress reschedules at ``t + period``; otherwise the
       component sleeps (``next_tick = +inf``);
    4. duplicate events are impossible by construction (wakes are ``min``-
       scatters into a single per-component wake time).
* Availability Backpropagation (paper Fig. 5): an incoming buffer going
  full→not-full wakes the serving connection; the connection draining a source
  port's outgoing buffer full→not-full wakes the upstream component — the
  backward chain that makes the sleep rules lossless.
* ``naive=True`` compiles the ablation engine — every component ticks every
  cycle of its clock, connections attempt delivery every cycle — used by the
  Fig. 9a/9b reproduction.  Both engines share the delivery/tick code, so the
  hypothesis equivalence test can require *bit-identical* results.

Hot-loop performance architecture (see ENGINE_PERF.md):

* **Segmented port state** — port ring buffers live in per-kind segments
  (``SimState.in_buf`` etc. are dicts keyed by kind name, mirroring
  ``comp_state``), so a kind's tick phase reads and writes *only its own
  segment*; the old layout needed a gather plus a full-array scatter per
  port array per kind per epoch.  ``_deliver`` materializes flat views
  lazily (concats of these small arrays are ~free) and is *scatter-free*:
  on CPU XLA a scatter costs two orders of magnitude more than the
  equivalent static-index take or one-hot select at these array sizes, so
  every dynamically-indexed update is reformulated as static takes
  (connection membership is a build-time constant) plus one-hot
  multiply/reduce over the destination-port axis.
* **Super-epoch fusion** — ``_run`` executes ``super_epoch`` (K) epochs per
  ``while_loop`` iteration via an inner ``lax.scan`` whose steps are guarded
  by ``lax.cond``: steps past the horizon are exact no-ops, so fused runs
  are bit-identical to K=1 runs while amortizing loop-condition evaluation
  and letting XLA fuse across epochs.  K is picked heuristically from the
  topology size and exposed as the ``super_epoch`` build knob (K=1 is the
  compatibility path).
* **Zero-copy stepping** — ``run()`` donates the ``SimState`` into the jitted
  loop (``donate_argnums``) so the big message buffers are updated in place
  instead of round-tripped; a donated input state must not be reused by the
  caller (use :meth:`Simulation.copy_state` first, or build with
  ``donate=False``).
* **Hoisted constants** — per-kind static index arrays (port slices, global
  port ids, capacity/peer slices, connection-membership masks) are
  precomputed once at build time instead of re-derived every epoch.
* **Static/traced split (DSE.md)** — structure (topology, wiring,
  capacities) stays a build-time constant, while the numeric timing/model
  knobs (connection latencies, per-kind tick periods, opt-in per-kind
  model params) live in a traced :class:`SimParams` pytree threaded
  through ``run()``: one compiled loop serves every design point of a
  structure, and ``repro.dse`` vmaps it over stacked param batches.

Parallelism is transparent exactly as the paper demands: ``tick_fn`` is
single-instance, lock-free code; the engine vmaps it over instances (VPU
lanes) and `repro.core.pdes` shards the instance axis over devices.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.bus import BUS

from .component import ComponentKind, KindHandle, normalize_tick_output
from .message import MSG_WORDS, W_DST, W_TIME, f2i, i2f
from .ports import EPS, Ports

INF = jnp.float32(jnp.inf)


def check_not_consumed(state) -> None:
    """Raise a clear error if ``state`` was already donated into a run.

    A donating ``run()`` consumes its input ``SimState`` — the buffers are
    released to the output (``is_deleted()`` turns true on the input's
    arrays).  Reusing it would otherwise surface as XLA's opaque
    deleted-buffer failure deep inside dispatch; this check turns that
    into an actionable message up front.
    """
    dead = [leaf for leaf in jax.tree.leaves(state)
            if getattr(leaf, "is_deleted", lambda: False)()]
    if dead:
        raise RuntimeError(
            "this SimState was already consumed by a donating run() — its "
            f"buffers are deleted ({len(dead)} leaves). Keep using the "
            "state a donating run *returns*; to reuse an input state, "
            "deep-copy it first (sim.copy_state(state)) or build the "
            "simulation with donate=False (see ENGINE_PERF.md).")


def _align_after(t, period):
    """First grid point of ``period`` strictly after ``t``."""
    return (jnp.floor(t / period + EPS) + 1.0) * period


def _align_at_or_after(t, period):
    """First grid point of ``period`` at or after ``t``."""
    return jnp.ceil(t / period - EPS) * period


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimParams:
    """Traced timing/model parameters of a compiled topology (DSE.md).

    The build splits the simulation's configuration in two: *structure*
    (topology, port wiring, buffer capacities, kind/instance counts) stays
    a hoisted build-time constant, while the *numeric knobs* below are a
    pytree threaded through the jitted hot loop as ordinary traced
    operands.  One compiled simulation therefore serves every design point
    that shares a structure: ``run(..., params=p)`` re-runs without
    recompiling, and ``repro.dse`` vmaps the loop over a stacked
    ``SimParams`` batch to simulate hundreds of configurations at once.

    Leaves (all shapes are per-topology static):
      * ``conn_latency`` — ``[C]`` f32 connection latencies in cycles
        (must stay >= 1; the no-zero-delay contract of ``connect`` is a
        structural invariant the trace cannot re-check).
      * ``periods`` — dict kind name -> ``[n_instances]`` f32 tick periods.
      * ``kind`` — dict kind name -> that kind's opt-in model-parameter
        pytree (``ComponentKind.params``; ``{}`` for kinds without one),
        passed as the 4th argument to a 4-ary ``tick_fn``.
      * ``inst_mask`` — dict kind name -> ``[n_instances]`` bool *activity
        masks* (``None`` = everything active, the default).  A masked-off
        instance never ticks, is pinned to ``next_tick = +inf`` (excluded
        from the next-event min) and contributes nothing to the tick/
        progress stats — so a *topology family* built at its maximum shape
        (``SimBuilder.build(pad_shape=...)``) simulates any sub-shape by
        mask alone, without rebuilding or recompiling (DSE.md).
      * ``conn_mask`` — ``[C]`` bool (``None`` = all active).  A masked-off
        connection never delivers and is pinned to ``conn_wake = +inf``.
        ``Simulation.prefix_masks`` derives both masks for a prefix
        sub-shape of a family.

    Params enter the loop as broadcast operands only — never as gather or
    scatter indices — so the scatter-free hot-loop property (ENGINE_PERF.md)
    is preserved under both tracing and batch vmapping; the masks in
    particular act through broadcast ``&``/``where`` selects.
    """

    conn_latency: jax.Array    # [C] f32
    periods: dict              # kind name -> [n_k] f32
    kind: dict                 # kind name -> params pytree ({} if none)
    inst_mask: Any = None      # kind name -> [n_k] bool, or None (all on)
    conn_mask: Any = None      # [C] bool, or None (all on)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Stats:
    epochs: jax.Array          # i32 — while-loop iterations executed
    ticks: jax.Array           # i32 — component ticks executed
    progress_ticks: jax.Array  # i32 — ticks that made forward progress
    delivered: jax.Array       # i32 — messages moved by connections
    busy: jax.Array            # [NC] i32 — per-component progressing ticks

    @staticmethod
    def zero(n_comp):
        # distinct buffers per field: aliased leaves cannot be donated
        z = lambda: jnp.zeros((), jnp.int32)
        return Stats(z(), z(), z(), z(), jnp.zeros((n_comp,), jnp.int32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimState:
    """Engine state.  Port arrays are *per-kind segments*: dicts keyed by
    kind name whose values are flat over that kind's ports
    (``[N_k * P_k, ...]``, instance-major).  Flat global views (ordered by
    kind registration, i.e. global port id) are materialized on demand via
    ``Simulation.flat_in_cnt`` and friends."""

    time: jax.Array            # f32 scalar — virtual time in cycles
    next_tick: jax.Array       # [NC] f32 — per-component wake time (+inf asleep)
    conn_wake: jax.Array       # [C] f32 — per-connection wake time
    comp_state: dict           # kind name -> pytree with leading [N_k]
    in_buf: dict               # kind name -> [NP_k, CAP, W] i32
    in_head: dict              # kind name -> [NP_k] i32
    in_cnt: dict               # kind name -> [NP_k] i32
    out_buf: dict              # kind name -> [NP_k, CAP, W] i32
    out_head: dict             # kind name -> [NP_k] i32
    out_cnt: dict              # kind name -> [NP_k] i32
    rr: jax.Array              # [C] i32 — round-robin pointers
    stats: Stats
    buf_samples: jax.Array     # [S, PG] i32 in-buffer levels (0-size if off)
    sample_idx: jax.Array      # i32
    next_sample: jax.Array     # f32


@dataclasses.dataclass
class _KindConsts:
    """Per-kind constants hoisted out of the hot loop at build time."""

    name: str
    n: int                     # instances
    p: int                     # ports per instance
    np_k: int                  # n * p
    cb: int                    # component base id
    pb: int                    # global port base id
    csl: slice                 # global component slice
    periods: jax.Array         # [n] f32
    caps: jax.Array            # [n, p] i32
    caps_f: jax.Array          # [n*p] i32
    gid: jax.Array             # [n, p] i32 global port ids
    peer: jax.Array            # [n, p] i32 default peers


class SimBuilder:
    """Builds a static topology: kinds, ports, connections (Akita §3.1)."""

    def __init__(self, msg_words: int = MSG_WORDS):
        assert msg_words == MSG_WORDS
        self.kinds: list[ComponentKind] = []
        self._kind_ix: dict[str, int] = {}
        self.conns: list[tuple[list[tuple[str, int, int]], float]] = []

    def add_kind(self, kind: ComponentKind) -> KindHandle:
        assert kind.name not in self._kind_ix, f"duplicate kind {kind.name}"
        self._kind_ix[kind.name] = len(self.kinds)
        self.kinds.append(kind)
        return KindHandle(kind.name, len(self.kinds) - 1)

    def connect(self, members, latency: float = 1.0):
        """Connect 2+ ports with a round-robin arbitrated crossbar.

        ``latency`` is in cycles and must be >= 1 (a "direct connection" is
        one cycle — no zero-delay loops; see DESIGN.md).
        """
        assert latency >= 1.0 - 1e-6, "connection latency must be >= 1 cycle"
        assert len(members) >= 2
        self.conns.append(([tuple(m) for m in members], float(latency)))
        return len(self.conns) - 1

    # ------------------------------------------------------------------
    def build(self, naive: bool = False, cap_phys: int | None = None,
              sample_period: float = 0.0, max_samples: int = 1024,
              super_epoch: int | None = None, donate: bool = True,
              pad_shape: dict[str, int] | None = None) -> "Simulation":
        """Compile the topology.

        ``super_epoch`` — epochs fused per ``while_loop`` iteration (None =
        heuristic from topology size, 1 = unfused compatibility path).
        ``donate`` — donate ``SimState`` into the jitted run so buffers are
        updated in place; callers must then treat the state passed to
        ``run()`` as consumed (see ENGINE_PERF.md).
        ``pad_shape`` — kind name -> instance count: size every named
        kind's segments to a *topology family* maximum before compiling
        (padded instances get zero-filled init rows and repeat the last
        declared period/capacity row).  Connections may wire the padded
        instances — membership is validated against the padded counts — so
        one build at the family maximum serves every sub-shape via the
        ``SimParams.inst_mask`` / ``conn_mask`` activity masks (DSE.md).
        """
        return Simulation(self, naive=naive, cap_phys=cap_phys,
                          sample_period=sample_period,
                          max_samples=max_samples,
                          super_epoch=super_epoch, donate=donate,
                          pad_shape=pad_shape)


def _pad_kind(k: ComponentKind, n_max: int) -> ComponentKind:
    """Pad a kind's instance axis to a family maximum: zero init rows,
    last-row periods/caps.  Padded rows only ever run when unmasked (a
    degenerate but legal all-active run); under ``inst_mask`` they are
    inert."""
    n = k.n_instances
    assert n_max >= n, f"pad_shape[{k.name!r}]={n_max} < declared {n}"
    if n_max == n:
        return k
    pad = n_max - n
    init = jax.tree.map(
        lambda a: jnp.concatenate(
            [jnp.asarray(a),
             jnp.zeros((pad,) + jnp.asarray(a).shape[1:],
                       jnp.asarray(a).dtype)]), k.init_state)
    periods = np.concatenate([k.periods(), np.repeat(k.periods()[-1:], pad)])
    caps = np.concatenate([k.caps(), np.repeat(k.caps()[-1:], pad, axis=0)])
    return dataclasses.replace(k, n_instances=n_max, init_state=init,
                               period=periods, cap=caps)


class Simulation:
    """A compiled-topology simulation instance."""

    def __init__(self, b: SimBuilder, naive: bool, cap_phys: int | None,
                 sample_period: float, max_samples: int,
                 super_epoch: int | None = None, donate: bool = True,
                 pad_shape: dict[str, int] | None = None):
        pad_shape = pad_shape or {}
        unknown = set(pad_shape) - {k.name for k in b.kinds}
        assert not unknown, f"pad_shape names unknown kinds {sorted(unknown)}"
        self.kinds = [_pad_kind(k, pad_shape[k.name])
                      if k.name in pad_shape else k for k in b.kinds]
        self.naive = naive
        self.donate = donate
        self.sample_period = float(sample_period)
        self.max_samples = int(max_samples) if sample_period > 0 else 0

        # --- component + port numbering ---------------------------------
        self.comp_base, self.port_base = [], []
        nc = pg = 0
        for k in self.kinds:
            self.comp_base.append(nc)
            self.port_base.append(pg)
            nc += k.n_instances
            pg += k.n_ports_total
        self.n_comp, self.n_ports_g = nc, pg

        if super_epoch is None:
            # Measured on CPU XLA (ENGINE_PERF.md): with the scatter-free
            # epoch body the loop boundary is cheap, so modest fusion is
            # enough; large topologies pay more per masked tail step and
            # per unrolled-copy compile time, so they stay unfused.
            super_epoch = 2 if pg <= 4096 else 1
        self.super_epoch = max(1, int(super_epoch))

        periods = np.concatenate([k.periods() for k in self.kinds]) \
            if self.kinds else np.zeros((0,), np.float32)
        caps = np.concatenate([k.caps().reshape(-1) for k in self.kinds]) \
            if self.kinds else np.zeros((0,), np.int32)
        self.cap_phys = int(cap_phys or max(4, caps.max(initial=1)))
        assert caps.max(initial=1) <= self.cap_phys

        # --- connections -------------------------------------------------
        def pid(ref):
            name, inst, port = ref
            ki = b._kind_ix[name]
            k = self.kinds[ki]
            assert 0 <= inst < k.n_instances and 0 <= port < k.n_ports, ref
            return self.port_base[ki] + inst * k.n_ports + port

        n_conn = max(1, len(b.conns))
        max_m = max([len(m) for m, _ in b.conns], default=2)
        member = np.full((n_conn, max_m), -1, np.int32)
        latency = np.ones((n_conn,), np.float32)
        port_conn = np.full((pg,), -1, np.int32)
        peer = np.full((pg,), -1, np.int32)
        for c, (members, lat) in enumerate(b.conns):
            pids = [pid(m) for m in members]
            assert len(set(pids)) == len(pids), "port connected twice"
            for j, p in enumerate(pids):
                assert port_conn[p] == -1, "each port is served by one connection"
                member[c, j] = p
                port_conn[p] = c
            latency[c] = lat
            if len(pids) == 2:
                peer[pids[0]], peer[pids[1]] = pids[1], pids[0]
        self.n_conn, self.max_m = n_conn, max_m

        # --- constants on device (only entries the hot loop / pdes still
        # read; member/latency/periods live on as the hoisted static copies
        # below and as SimParams defaults — edit those, not this dict) ----
        self.c = dict(
            caps=jnp.asarray(caps), port_conn=jnp.asarray(port_conn),
            peer=jnp.asarray(peer),
        )
        self._periods_np, self._caps_np = periods, caps
        self._latency_np = latency
        # --- hoisted delivery constants (scatter-free formulation) -------
        # slot_of_port: inverse of the member matrix — each port is served
        # by at most one connection slot, so winner pops become static takes.
        CM = n_conn * max_m
        slot = np.full((pg + 1,), CM, np.int32)
        flat_m = member.reshape(-1)
        for sl_ix, g in enumerate(flat_m):
            if g >= 0:
                slot[g] = sl_ix
        self._slot_of_port = slot[:pg]
        self._mps_np = np.maximum(member, 0)
        self._valid_np = member >= 0
        self._mps_j = jnp.asarray(self._mps_np)
        # member matrix with invalid slots pointing past the wake-mask pad
        self._member_sent_np = np.where(member >= 0, member, pg)
        self._apg = np.arange(pg, dtype=np.int32)                 # [PG]
        self._acap = np.arange(self.cap_phys, dtype=np.int32)     # [CAP]
        self._am = np.arange(max_m, dtype=np.int32)               # [M]
        self._acm = np.arange(CM, dtype=np.int32)                 # [C*M]
        self._build_kind_consts()
        self._dp = self.default_params()
        # until/max_epochs are traced operands (not static): one compiled
        # loop serves every horizon and epoch budget, and repro.dse can
        # vmap them per lane so each lane freezes at its own horizon.
        self._jit_kwargs: dict[str, Any] = {}
        if donate:
            self._jit_kwargs["donate_argnums"] = (0,)
        self._run_jit = jax.jit(self._run, **self._jit_kwargs)

    # ------------------------------------------------------------------
    def _build_kind_consts(self):
        """Hoist per-kind static index/constant arrays out of the hot loop."""
        self._kc = []
        peer = np.asarray(self.c["peer"])
        for ki, k in enumerate(self.kinds):
            n, p = k.n_instances, k.n_ports
            np_k = n * p
            cb, pb = self.comp_base[ki], self.port_base[ki]
            self._kc.append(_KindConsts(
                name=k.name, n=n, p=p, np_k=np_k, cb=cb, pb=pb,
                csl=slice(cb, cb + n),
                periods=jnp.asarray(self._periods_np[cb:cb + n]),
                caps=jnp.asarray(self._caps_np[pb:pb + np_k].reshape(n, p)),
                caps_f=jnp.asarray(self._caps_np[pb:pb + np_k]),
                gid=jnp.arange(pb, pb + np_k, dtype=jnp.int32).reshape(n, p),
                peer=jnp.asarray(peer[pb:pb + np_k].reshape(n, p))))

    def default_params(self) -> SimParams:
        """The :class:`SimParams` this topology was built with.

        Running with ``params=None`` is equivalent to (and compiles the
        same program as) running with these values baked in as constants;
        override leaves (or stack many variants — ``repro.dse``) to
        explore other design points without rebuilding or recompiling.
        """
        return SimParams(
            conn_latency=jnp.asarray(self._latency_np),
            periods={kc.name: kc.periods for kc in self._kc},
            kind={k.name: (jax.tree.map(jnp.asarray, k.params)
                           if k.params is not None else {})
                  for k in self.kinds})

    def prefix_masks(self, counts: dict[str, int]
                     ) -> tuple[dict, jax.Array]:
        """Activity masks for a *prefix sub-shape* of this topology.

        ``counts`` maps kind names to active instance counts (unnamed
        kinds stay fully active); instances ``0..count-1`` of each kind
        are active.  Returns ``(inst_mask, conn_mask)`` for
        :class:`SimParams`: a connection is active iff any of its member
        ports belongs to an active instance — so per-instance links
        between masked instances go quiet while shared fabrics (a family
        crossbar with masked member ports) stay live.

        The prefix discipline is what keeps masked runs bit-identical to
        an unpadded build of the sub-shape (DSE.md): variable-count
        members must occupy the leading member slots of their connection
        in instance order, fixed members the trailing slots, so
        round-robin arbitration sees the same relative slot order at
        every shape.
        """
        unknown = set(counts) - {k.name for k in self.kinds}
        assert not unknown, f"unknown kinds {sorted(unknown)}"
        inst, act = {}, []
        for k in self.kinds:
            n = int(counts.get(k.name, k.n_instances))
            assert 0 <= n <= k.n_instances, (k.name, n, k.n_instances)
            m = np.arange(k.n_instances) < n
            inst[k.name] = jnp.asarray(m)
            act.append(np.repeat(m, k.n_ports))
        port_act = (np.concatenate(act) if act else np.zeros((0,), bool))
        conn = np.any(self._valid_np & port_act[self._mps_np], axis=1)
        return inst, jnp.asarray(conn)

    def _flat_inst_mask(self, inst_mask: dict) -> jax.Array:
        """[NC] bool — per-component activity, ordered by kind
        registration (component id order)."""
        parts = [inst_mask[k.name] for k in self.kinds]
        if not parts:
            return jnp.zeros((0,), bool)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def set_default_peers(self, mapping: dict[int, int]):
        """Rewrite default peers (global port id -> peer port id) and refresh
        the hoisted per-kind constants.  Safe at any time: the jitted run is
        re-wrapped so traces that baked the old constants are discarded."""
        peer = np.asarray(self.c["peer"]).copy()
        for src, dst in mapping.items():
            peer[src] = dst
        self.c["peer"] = jnp.asarray(peer)
        self._build_kind_consts()
        self._run_jit = jax.jit(self._run, **self._jit_kwargs)

    # ------------------------------------------------------------------
    def port_id(self, kind_name: str, inst: int, port: int = 0) -> int:
        """Global port id for (kind, instance, port) — for explicit addressing."""
        for ki, k in enumerate(self.kinds):
            if k.name == kind_name:
                assert 0 <= inst < k.n_instances and 0 <= port < k.n_ports
                return self.port_base[ki] + inst * k.n_ports + port
        raise KeyError(kind_name)

    def comp_id(self, kind_name: str, inst: int) -> int:
        for ki, k in enumerate(self.kinds):
            if k.name == kind_name:
                return self.comp_base[ki] + inst
        raise KeyError(kind_name)

    # ------------------------------------------------------------------
    def _flat(self, seg: dict) -> jax.Array:
        """Flat global view (ordered by kind => global port id) of a
        per-kind segment dict."""
        parts = [seg[k.name] for k in self.kinds]
        if not parts:
            return jnp.zeros((0,), jnp.int32)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def flat_in_cnt(self, s: SimState) -> jax.Array:
        return self._flat(s.in_cnt)

    def flat_out_cnt(self, s: SimState) -> jax.Array:
        return self._flat(s.out_cnt)

    def copy_state(self, s: SimState) -> SimState:
        """Deep-copy a state so the original survives a donating ``run()``."""
        return jax.tree.map(jnp.copy, s)

    # ------------------------------------------------------------------
    def init_state(self) -> SimState:
        with BUS.span("engine.init_state"):
            cap, w = self.cap_phys, MSG_WORDS
            next_tick = []
            for k in self.kinds:
                t0 = INF if k.start_asleep else 0.0
                next_tick.append(jnp.full((k.n_instances,), t0, jnp.float32))
            seg = lambda shape_fn: {kc.name: shape_fn(kc) for kc in self._kc}
            zeros_np = lambda kc: jnp.zeros((kc.np_k,), jnp.int32)
            zeros_buf = lambda kc: jnp.zeros((kc.np_k, cap, w), jnp.int32)
            # copy user-supplied init pytrees: donation must never delete (or
            # double-donate aliases of) the builder's arrays
            comp_state = jax.tree.map(
                jnp.copy, {k.name: k.init_state for k in self.kinds})
            return SimState(
                time=jnp.float32(0.0),
                next_tick=(jnp.concatenate(next_tick) if next_tick
                           else jnp.zeros((0,), jnp.float32)),
                conn_wake=jnp.full((self.n_conn,), INF),
                comp_state=comp_state,
                in_buf=seg(zeros_buf), in_head=seg(zeros_np),
                in_cnt=seg(zeros_np),
                out_buf=seg(zeros_buf), out_head=seg(zeros_np),
                out_cnt=seg(zeros_np),
                rr=jnp.zeros((self.n_conn,), jnp.int32),
                stats=Stats.zero(self.n_comp),
                # min 1 row: zero-sized arrays break shard_map sharding (pdes)
                buf_samples=jnp.zeros(
                    (max(self.max_samples, 1), self.n_ports_g), jnp.int32),
                sample_idx=jnp.int32(0),
                next_sample=jnp.float32(
                    self.sample_period if self.sample_period else jnp.inf),
            )

    def _port_min_to_comp(self, wake_port):
        """Per-port wake times [PG] -> per-component wake times [NC] by a
        min over each component's (contiguous) ports — static reshapes, no
        scatter."""
        if not self.kinds:
            return jnp.zeros((0,), jnp.float32)
        parts = [
            jnp.min(wake_port[kc.pb:kc.pb + kc.np_k].reshape(kc.n, kc.p),
                    axis=1)
            for kc in self._kc]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    # ------------------------------------------------------------------
    # Delivery phase: round-robin arbitrated crossbar per connection.
    #
    # Scatter-free: on CPU XLA a scatter costs two orders of magnitude more
    # than the equivalent take/one-hot arithmetic at these array sizes.
    # Connection membership is static, so source-side pops are static takes
    # through ``slot_of_port``; destination-side state is computed *per
    # port* — round-robin arbitration admits at most one winner per
    # destination port per connection, so a [C*M, PG] one-hot reduces
    # exactly to each port's winning slot, and pushes become masked selects
    # on each kind's segment.  A message's dst must be a port of its
    # serving connection (the crossbar contract; arbitration cannot see
    # across connections — the previous scatter formulation corrupted
    # cross-connection collisions just the same, via double in_cnt adds).
    # Traced params (SimParams) enter this phase as broadcast operands only:
    # per-connection latency is repeated over the (static) member axis and
    # per-kind periods over each kind's (static) port count — both are
    # shape-preserving broadcasts XLA folds to constants when the params are
    # the build-time defaults, keeping the params=None path bit- and
    # schedule-identical to the pre-params engine.
    def _deliver(self, s: SimState, P: SimParams, t, active, wake1):
        if not self.kinds:
            return s, jnp.zeros((0,), jnp.float32)
        c = self.c
        lat_f = jnp.repeat(P.conn_latency, self.max_m)            # [C*M]
        pp = [jnp.repeat(P.periods[kc.name], kc.p) for kc in self._kc]
        port_period = pp[0] if len(pp) == 1 else jnp.concatenate(pp)  # [PG]
        C, M, PG = self.n_conn, self.max_m, self.n_ports_g
        CM = C * M
        mps, valid = self._mps_np, jnp.asarray(self._valid_np)   # [C, M]
        # flat views of the per-port arrays (cheap concats at these sizes)
        in_head_f, in_cnt_f = self._flat(s.in_head), self._flat(s.in_cnt)
        out_head_f, out_cnt_f = self._flat(s.out_head), self._flat(s.out_cnt)
        out_buf_f = self._flat(s.out_buf)

        have = (out_cnt_f[mps] > 0) & valid & active[:, None]
        head_ix = out_head_f[mps]                        # [C, M]
        head = out_buf_f[self._mps_j, head_ix]           # [C, M, W]
        dst = head[:, :, W_DST]
        dsts = jnp.clip(dst, 0, PG - 1)
        OH0 = dsts.reshape(CM)[:, None] == self._apg     # [CM, PG] one-hot
        space_port = in_cnt_f < c["caps"]                # [PG]
        space = jnp.any(OH0 & space_port[None, :], axis=1).reshape(C, M)
        req = have & space & (dst >= 0)
        prio = (self._am[None, :] - s.rr[:, None]) % M
        # m loses if some m2 requests the same destination with lower prio.
        beats = (req[:, None, :] & (dst[:, :, None] == dst[:, None, :])
                 & (prio[:, None, :] < prio[:, :, None]))
        win = req & ~jnp.any(beats, axis=2)              # [C, M]
        win_f = win.reshape(CM)
        OHwin = OH0 & win_f[:, None]                     # [CM, PG]

        # per destination port: did it receive, and from which member slot
        got = jnp.any(OHwin, axis=0)                     # [PG]
        wslot = jnp.sum(OHwin * self._acm[:, None], axis=0)       # [PG]
        arrive = t + lat_f                               # [CM]
        msg_f = head.reshape(CM, MSG_WORDS).at[:, W_TIME].set(f2i(arrive))
        msg_port = msg_f[wslot]                          # [PG, W]
        arr_port = jnp.where(got, arrive[wslot], INF)    # [PG]
        t_port = (in_head_f + in_cnt_f) % self.cap_phys
        capOH = (t_port[:, None] == self._acap) & got[:, None]    # [PG, CAP]
        goti = got.astype(jnp.int32)

        # source-side pops: static take (each port has one member slot)
        win_pad = jnp.concatenate([win_f, jnp.zeros((1,), bool)])
        dec = win_pad[self._slot_of_port].astype(jnp.int32)       # [PG]
        full_before_out = out_cnt_f == c["caps"]

        # Rule 1: arrival wakes the destination; rule 2 / backprop forward
        # half: freed source out-buffer wakes its owner.  Both computed per
        # port, then min-reduced onto components (ports are owner-major).
        freed_port = (dec > 0) & full_before_out
        wake_port = jnp.minimum(
            _align_at_or_after(arr_port, port_period),
            jnp.where(freed_port, _align_after(t, port_period), INF))
        wake_comp = self._port_min_to_comp(wake_port)

        # per-kind segment updates (pure where/add on each segment slice)
        out_cnt_seg, out_head_seg = dict(s.out_cnt), dict(s.out_head)
        in_buf_seg, in_cnt_seg = dict(s.in_buf), dict(s.in_cnt)
        for kc in self._kc:
            sl = slice(kc.pb, kc.pb + kc.np_k)
            out_cnt_seg[kc.name] = s.out_cnt[kc.name] - dec[sl]
            out_head_seg[kc.name] = (s.out_head[kc.name]
                                     + dec[sl]) % self.cap_phys
            in_cnt_seg[kc.name] = s.in_cnt[kc.name] + goti[sl]
            in_buf_seg[kc.name] = jnp.where(
                capOH[sl][:, :, None], msg_port[sl][:, None, :],
                s.in_buf[kc.name])

        # round-robin pointer: advance past the last-served winner
        gp = jnp.where(win, prio, -1)
        any_win = jnp.any(win, axis=1)
        last = jnp.argmax(gp, axis=1).astype(jnp.int32)
        rr = jnp.where(any_win, (last + 1) % M, s.rr)

        # connection self-scheduling: if it delivered and work remains, wake
        # next cycle; otherwise sleep (backprop / sends will wake it).
        out_cnt_f2 = out_cnt_f - dec
        pending = jnp.any(valid & (out_cnt_f2[mps] > 0), axis=1)
        nw = jnp.where(any_win & pending, wake1, INF)
        conn_wake = jnp.where(active, nw, s.conn_wake)

        delivered = jnp.sum(win_f.astype(jnp.int32))
        s = dataclasses.replace(
            s, in_buf=in_buf_seg, in_cnt=in_cnt_seg,
            out_cnt=out_cnt_seg, out_head=out_head_seg, rr=rr,
            conn_wake=conn_wake,
            stats=dataclasses.replace(s.stats,
                                      delivered=s.stats.delivered + delivered))
        return s, wake_comp

    # ------------------------------------------------------------------
    # Tick phase: vmap each kind's tick_fn over its instances; with the
    # segmented layout each kind reads/writes only its own segment.
    def _tick_kinds(self, s: SimState, P: SimParams, t, wake1):
        next_tick = s.next_tick
        comp_state = dict(s.comp_state)
        in_buf, in_head, in_cnt = dict(s.in_buf), dict(s.in_head), dict(s.in_cnt)
        out_buf, out_head, out_cnt = (dict(s.out_buf), dict(s.out_head),
                                      dict(s.out_cnt))
        total_ticks = jnp.int32(0)
        total_prog = jnp.int32(0)
        busy = s.stats.busy
        tf = jnp.asarray(t, jnp.float32)
        wake_p_segs = {}           # kind -> [n*p] bool: port wants its conn

        for ki, kind in enumerate(self.kinds):
            kc = self._kc[ki]
            n, p, name = kc.n, kc.p, kc.name
            with jax.named_scope(f"engine.tick.{name}"):
                periods_k = P.periods[name]
                if self.naive:
                    r = jnp.remainder(t, periods_k)
                    mask = (jnp.abs(r) < EPS) | (jnp.abs(r - periods_k) < EPS)
                else:
                    mask = next_tick[kc.csl] <= t + EPS
                if P.inst_mask is not None:
                    # family activity mask: masked-off instances never tick
                    # (and therefore never count toward ticks/progress/busy)
                    mask = mask & P.inst_mask[name]

                sh = lambda a: a.reshape(n, p, *a.shape[1:])
                # kind params are closed over, not vmapped: every instance of a
                # kind sees the same (possibly traced) parameter pytree
                kp = P.kind.get(name, {})
                wants_params = kind.params is not None

                def one(st_i, ib, ih, ic, ob, oh, oc, cp, g, pe, kind=kind,
                        kp=kp, wants_params=wants_params):
                    ports = Ports(ib, ih, ic, ob, oh, oc, cp, g, pe, tf)
                    out = (kind.tick_fn(st_i, ports, tf, kp) if wants_params
                           else kind.tick_fn(st_i, ports, tf))
                    st2, ports2, res = normalize_tick_output(out)
                    return (st2, ports2.in_buf, ports2.in_head, ports2.in_cnt,
                            ports2.out_buf, ports2.out_head, ports2.out_cnt,
                            res.progress, res.next_time)

                (st2, ib2, ih2, ic2, ob2, oh2, oc2, prog, nxt) = jax.vmap(one)(
                    comp_state[name], sh(in_buf[name]), sh(in_head[name]),
                    sh(in_cnt[name]), sh(out_buf[name]), sh(out_head[name]),
                    sh(out_cnt[name]), kc.caps, kc.gid, kc.peer)

                def sel(new, old, m=mask):
                    mm = m.reshape(m.shape + (1,) * (new.ndim - 1))
                    return jnp.where(mm, new, old)

                comp_state[name] = jax.tree.map(
                    lambda a, b: sel(a, b), st2, comp_state[name])
                fl = lambda a: a.reshape(n * p, *a.shape[2:])
                pmask = jnp.repeat(mask, p)

                def psel(new, old):
                    mm = pmask.reshape(pmask.shape + (1,) * (new.ndim - 1))
                    return jnp.where(mm, new, old)

                ic_old, oc_old = in_cnt[name], out_cnt[name]
                in_buf[name] = psel(fl(ib2), in_buf[name])
                in_head[name] = psel(fl(ih2), in_head[name])
                in_cnt[name] = psel(fl(ic2), in_cnt[name])
                out_buf[name] = psel(fl(ob2), out_buf[name])
                out_head[name] = psel(fl(oh2), out_head[name])
                out_cnt[name] = psel(fl(oc2), out_cnt[name])

                prog = prog & mask
                if not self.naive:
                    # Rule 3: progress => next cycle; no progress => sleep.
                    base = jnp.where(prog, _align_after(t, periods_k), INF)
                    custom = jnp.where(nxt > -0.5,
                                       jnp.maximum(nxt, t + EPS), base)
                    # In-flight arrivals: a ticked component must not sleep
                    # past the ready time of a message already in its
                    # buffers (rule 1 for arrivals whose delivery preceded
                    # this tick).  Ready-now messages do NOT re-wake —
                    # unblocking is backprop's job.
                    hb = in_buf[name][:, :, W_TIME]             # [n*p, CAP]
                    hOH = in_head[name][:, None] == self._acap  # one-hot
                    hr = i2f(jnp.sum(hb * hOH.astype(jnp.int32), axis=1))
                    pend = (in_cnt[name] > 0) & (hr > t + EPS)
                    w = jnp.where(pend, hr, INF).reshape(n, p)
                    arr = _align_at_or_after(jnp.min(w, axis=1), periods_k)
                    custom = jnp.minimum(custom, arr)
                    next_tick = next_tick.at[kc.csl].set(
                        jnp.where(mask, custom, next_tick[kc.csl]))

                # Availability Backpropagation (backward half): incoming
                # buffer full->not-full wakes the serving connection; any
                # new send wakes the connection too.
                ic_new, oc_new = in_cnt[name], out_cnt[name]
                in_freed = (ic_old == kc.caps_f) & (ic_new < kc.caps_f)
                wake_p_segs[name] = in_freed | (oc_new > oc_old)

                total_ticks += jnp.sum(mask.astype(jnp.int32))
                total_prog += jnp.sum(prog.astype(jnp.int32))
                busy = busy.at[kc.csl].add(prog.astype(jnp.int32))

        with jax.named_scope("engine.update"):
            # a connection wakes iff any of its (static) member ports asked —
            # static take through the member matrix instead of a scatter-min
            if self.kinds:
                wake_p_f = self._flat(wake_p_segs)
                wake_pad = jnp.concatenate([wake_p_f, jnp.zeros((1,), bool)])
                conn_asked = jnp.any(wake_pad[self._member_sent_np], axis=1)
                wake_conn = jnp.where(conn_asked, wake1, INF)
            else:
                wake_conn = jnp.full((self.n_conn,), INF)

            stats = dataclasses.replace(
                s.stats, ticks=s.stats.ticks + total_ticks,
                progress_ticks=s.stats.progress_ticks + total_prog, busy=busy)
            s = dataclasses.replace(
                s, next_tick=next_tick, comp_state=comp_state, in_buf=in_buf,
                in_head=in_head, in_cnt=in_cnt, out_buf=out_buf,
                out_head=out_head, out_cnt=out_cnt, stats=stats)
        return s, wake_conn

    # ------------------------------------------------------------------
    def _epoch(self, s: SimState, P: SimParams):
        # the named scopes only label the ops in the compiled program's
        # metadata (profiler op names); they change no computation
        with jax.named_scope("engine.next_event"):
            if self.naive:
                t = s.time  # process the current cycle, then advance by one
                active = jnp.ones((self.n_conn,), bool)
            else:
                t = jnp.minimum(jnp.min(s.next_tick) if self.n_comp else INF,
                                jnp.min(s.conn_wake))
                if self.max_samples:
                    t = jnp.minimum(t, s.next_sample)
                active = s.conn_wake <= t + EPS
            if P.conn_mask is not None:
                # family activity mask: masked-off connections never deliver
                active = active & P.conn_mask
            wake1 = _align_after(t, 1.0)      # shared next-cycle wake point

        s = dataclasses.replace(s, time=t)
        with jax.named_scope("engine.deliver"):
            s, wake_comp = self._deliver(s, P, t, active, wake1)
        s, wake_conn = self._tick_kinds(s, P, t, wake1)
        with jax.named_scope("engine.update"):
            next_tick = jnp.minimum(s.next_tick, wake_comp)
            conn_wake = jnp.minimum(s.conn_wake, wake_conn)
            # Masked-off rows are pinned to +inf by broadcast selects so the
            # next-event min never schedules them — the mask's only entry
            # points into the wake reductions (no gathers/scatters involved).
            if P.inst_mask is not None:
                next_tick = jnp.where(self._flat_inst_mask(P.inst_mask),
                                      next_tick, INF)
            if P.conn_mask is not None:
                conn_wake = jnp.where(P.conn_mask, conn_wake, INF)
            s = dataclasses.replace(
                s, next_tick=next_tick, conn_wake=conn_wake,
                stats=dataclasses.replace(s.stats, epochs=s.stats.epochs + 1))
            if self.max_samples:
                do = s.next_sample <= t + EPS
                row = s.sample_idx % self.max_samples
                s = dataclasses.replace(
                    s,
                    buf_samples=jnp.where(
                        do, s.buf_samples.at[row].set(self._flat(s.in_cnt)),
                        s.buf_samples),
                    sample_idx=s.sample_idx + do.astype(jnp.int32),
                    next_sample=jnp.where(
                        do, s.next_sample + self.sample_period,
                        s.next_sample))
            if self.naive:
                s = dataclasses.replace(s, time=t + 1.0)
        return s

    def _next_event(self, s: SimState):
        t = jnp.min(s.next_tick) if self.n_comp else INF
        t = jnp.minimum(t, jnp.min(s.conn_wake))
        if self.max_samples:
            t = jnp.minimum(t, s.next_sample)
        return t

    def _live(self, s: SimState, until, max_epochs):
        """Liveness predicate of the hot loop: events remain before the
        horizon AND the epoch budget is not exhausted.  ``until`` and
        ``max_epochs`` are ordinary traced operands, so ``repro.dse`` can
        vmap this per lane (per-lane horizons) and poll it cheaply between
        rounds without recompiling anything."""
        if self.naive:
            more = s.time <= until + EPS
        else:
            more = self._next_event(s) <= until + EPS
        return more & (s.stats.epochs < max_epochs)

    def _run(self, s: SimState, until, max_epochs,
             params: SimParams | None = None):
        P = self._dp if params is None else params
        until = jnp.asarray(until, jnp.float32)
        max_epochs = jnp.asarray(max_epochs, jnp.int32)
        cond = lambda s: self._live(s, until, max_epochs)
        if self.super_epoch <= 1:
            return jax.lax.while_loop(cond, lambda s: self._epoch(s, P), s)

        # Super-epoch fusion: K epochs per while iteration.  Each inner step
        # re-checks liveness and is an exact no-op (lax.cond identity) once
        # the horizon/epoch budget is reached, so results are bit-identical
        # to the K=1 path while the loop condition round-trip is amortized
        # K-fold and XLA can fuse across the unrolled steps.
        def body(s):
            def step(s, _):
                s = jax.lax.cond(self._live(s, until, max_epochs),
                                 lambda x: self._epoch(x, P), lambda x: x, s)
                return s, None
            s, _ = jax.lax.scan(step, s, None, length=self.super_epoch,
                                unroll=True)
            return s

        return jax.lax.while_loop(cond, body, s)

    def run(self, state: SimState, until: float,
            max_epochs: int = 2_000_000,
            params: SimParams | None = None) -> SimState:
        """Advance the simulation to virtual time ``until`` (cycles).

        When the simulation was built with ``donate=True`` (the default),
        ``state``'s buffers are donated to the jitted loop and must not be
        reused afterwards — keep using the *returned* state, or pass
        ``copy_state(state)`` if the input must survive.

        ``until`` and ``max_epochs`` are *traced* operands: changing
        either re-runs the same compiled loop (no recompile), and batched
        runs (``repro.dse``) may pass per-lane values so every lane
        freezes at its own horizon / epoch budget.

        ``params`` (optional) overrides the traced timing/model parameters
        for this run (see :class:`SimParams` / ``default_params()``); its
        leaves are never donated.  ``None`` runs the build-time defaults."""
        assert until < 2 ** 24, "float32 cycle precision bound (DESIGN.md)"
        with BUS.span("engine.run"):
            if self.donate:
                check_not_consumed(state)
            return self._run_jit(state, until, max_epochs=max_epochs,
                                 params=params)
