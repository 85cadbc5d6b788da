"""Batched design-space execution: vmap the engine's fused hot loop over a
stacked :class:`~repro.core.SimParams` batch.

One jitted program simulates every design point of a topology at once:
``jax.vmap`` maps the ``while_loop`` body over the config axis.  The
horizon and epoch budget are *traced per-lane operands* — each lane
freezes bit-exactly at its own ``until`` / ``max_epochs`` (the batching
rule selects the old carry for finished lanes), so a B=1 batch is
*bit-identical* to the unbatched engine and mixed-horizon lanes are
first-class.  Params enter the loop as broadcast operands only, so the
scatter-free hot-loop property (ENGINE_PERF.md) survives batching.

Execution strategies, cheapest lane-waste first:

* **Rounds** (``run_rounds``, what ``run_sweep`` uses) — the
  straggler-free path: run a bounded epoch *quantum*, pull the cheap
  per-lane liveness vector to host, drop finished lanes, compact the
  survivors (a device gather outside the jitted loop) into a rung of the
  geometric **chunk ladder** (``repro.dse.schedule``) and refill from the
  pending-config queue.  A monolithic batch runs every lane to the
  *slowest* lane's horizon — finished lanes burn full masked epochs — and
  large B can fall below sequential shared-jit throughput; rounds stream
  arbitrary B through a handful of cached executables (one per rung, zero
  recompiles after warmup) at the autotuned batch width.  The loop is a
  depth-2 software pipeline by default: round *k+1* is assembled and
  dispatched while round *k* computes on device and its liveness copy
  streams to host asynchronously, so host bookkeeping overlaps device
  work (ENGINE_PERF.md "Round pipelining"; ``pipeline=False`` restores
  the strictly alternating loop, bit-identically).
* **Chunking** — ``run_chunked(chunk=...)`` splits B into fixed-size
  slabs (no mid-run compaction); the final partial slab is padded with
  *zero-horizon* lanes that freeze on entry instead of re-simulating the
  repeated tail point.
* **Sharding** — ``shard=True`` (or ``shard=<n devices>``) lays the
  batch out as ``[shards, chunk]`` lanes over an explicit 1-D device
  mesh (``core.pdes.lane_mesh``) and runs **one** ``shard_map``-of-vmap
  executable across the whole mesh per round; with one device this is
  the plain vmap path and results are bit-identical either way (lanes
  are independent — a mesh only changes where each lane's arithmetic
  runs).  Batches that don't divide the device count are padded with
  zero-horizon lanes (freeze on entry) instead of shrinking to a
  divisor, so every device stays busy at any B.  Under ``run_rounds``
  the harvest/compact/refill step is *global*: survivors from all
  shards pool on the host and re-pack across shards each round, so a
  shard that drains early picks up its neighbours' pending lanes
  instead of idling (``shard.rebalance`` telemetry counts the moves).

Cold-start cost is covered by ``repro.dse.cache`` (DSE.md "Sharded
sweeps and the persistent cache"): ``run_sweep`` enables the jax
persistent compilation cache on entry when a cache dir is configured,
and the runner persists its own warm-start artifacts (autotuned rung,
warm-ladder rung set, family shape unions) so a fresh process repeats a
previous process's executable requests exactly.
* **Donation** — batched states are donated into the loop exactly like
  the unbatched engine (build knob ``donate=``); ``stack_states``
  materializes fresh per-lane copies so no lane aliases another lane or
  the template state (donating an aliased batch would corrupt sibling
  configs).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import inspect
import time
import weakref
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core import SimParams, SimState, check_not_consumed
from repro.core.pdes import LANE_AXIS, lane_mesh
from repro.obs.bus import BUS

from . import cache as dse_cache
from .family import TopologyFamily
from .schedule import ChunkSchedule, ChunkAutotuner, auto_schedule
from .sweep import (STATIC_PREFIX, SweepSpec, apply_point,
                    build_param_batch, split_shape, stack_params,
                    stack_trees)

INT32_MAX = np.int32(2**31 - 1)


@dataclasses.dataclass(frozen=True)
class ResumeHandle:
    """A frozen lane's continuation point: the final :class:`SimState` of
    a finished run plus where it stopped.

    The engine's horizon is an absolute traced operand and its epoch
    sequence is purely state-determined, so feeding ``state`` back in as
    a lane's initial state and running to a *longer* ``until`` continues
    bit-exactly where the run froze — the warm-promotion contract of
    ``repro.dse.search`` (a resumed lane equals a cold run to the same
    horizon, pinned by ``tests/dse/test_warm_resume.py``).  ``time`` and
    ``epochs`` let budget accounting charge only the increment and the
    round loop cap epochs correctly from the first round.
    """

    state: SimState
    time: float        # frozen virtual_time
    until: float       # horizon the state was run to
    epochs: int        # engine epochs executed so far


class LaneStates:
    """Lazy per-point access to the final states of a finished sweep.

    ``run_sweep(return_states=True)`` hands every group's stacked final
    state to one of these, reusing the single host transfer the row
    extraction already paid — no extra device syncs.  Only the lanes a
    caller actually asks for are sliced (a halving search touches the
    survivors, not the whole rung).  ``handle(i, until)`` packages lane
    ``i`` as a :class:`ResumeHandle` for a later warm resume.
    """

    def __init__(self):
        self._groups: list = []            # host-side stacked trees
        self._where: dict[int, tuple[int, int]] = {}

    def add_group(self, host_tree, indices: Sequence[int]) -> None:
        g = len(self._groups)
        self._groups.append(host_tree)
        for j, i in enumerate(indices):
            self._where[int(i)] = (g, j)

    def __contains__(self, i) -> bool:
        return int(i) in self._where

    def __len__(self) -> int:
        return len(self._where)

    def state(self, i: int) -> SimState:
        g, j = self._where[int(i)]
        return lane(self._groups[g], j)

    def time(self, i: int) -> float:
        g, j = self._where[int(i)]
        return float(self._groups[g].time[j])

    def epochs(self, i: int) -> int:
        g, j = self._where[int(i)]
        return int(self._groups[g].stats.epochs[j])

    def handle(self, i: int, until: float) -> ResumeHandle:
        return ResumeHandle(state=self.state(i), time=self.time(i),
                            until=float(until), epochs=self.epochs(i))


def stack_states(state: SimState, n: int) -> SimState:
    """``n`` independent copies of ``state`` stacked on a new leading axis.

    ``jnp.stack`` materializes one fresh buffer per leaf — lanes never
    alias each other or the input, so the result is safe to donate while
    ``state`` stays reusable as a template.
    """
    return _stack(state, n)


# Round assembly and compaction move lanes of whole state trees with
# batch sizes that change from round to round.  Eager per-leaf ops
# compile one program per (leaf, shape) — about a thousand compiles for a
# 256-point campaign, each a fraction of a second on a TPU — where one
# jitted program per shape combination moves every leaf at once.
@functools.partial(jax.jit, static_argnums=1)
def _stack(state, n: int):
    return jax.tree.map(lambda x: jnp.stack([x] * n), state)


@jax.jit
def _take(tree, idx):
    """Lanes ``idx`` (in that order) of every leaf of a batched tree."""
    return jax.tree.map(lambda x: x[idx], tree)


@jax.jit
def _cat(parts):
    """Batched trees concatenated along the lane axis."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


def stack_state_list(states: Sequence[SimState]) -> SimState:
    """Stack *distinct* per-lane states (e.g. one per family sub-shape)
    into a batch.  Fresh buffers per leaf, like :func:`stack_states`."""
    return stack_trees(states)


def lane(tree, i: int):
    """Extract config ``i``'s slice from a batched pytree (device- or
    host-side — works on jax arrays and on the numpy tree a single
    ``jax.device_get`` returns)."""
    return jax.tree.map(lambda x: x[i], tree)


def default_extract(sim, s: SimState) -> dict:
    """Per-config scalar results: virtual time + engine counters.

    ``run_sweep`` hands this *host-side* lanes (one ``jax.device_get``
    of the whole chunk, sliced on host), so the ``float()``/``int()``
    casts below are free; on a raw device lane each cast would be its
    own device→host sync.
    """
    return {
        "virtual_time": float(s.time),
        "epochs": int(s.stats.epochs),
        "ticks": int(s.stats.ticks),
        "progress_ticks": int(s.stats.progress_ticks),
        "delivered": int(s.stats.delivered),
    }


def extract_rows(sim, out_b: SimState, n: int,
                 extract: Callable | None = None) -> list[dict]:
    """Extract ``n`` result rows from a batched final state with a single
    device→host transfer.

    One ``jax.device_get`` pulls the whole stacked tree at once; lanes
    are then sliced on host, so an extractor touching k scalar fields
    costs 1 transfer total instead of ``n * k`` syncs.
    """
    extract = extract or default_extract
    host = jax.device_get(out_b)
    return [extract(sim, lane(host, j)) for j in range(n)]


def _vec(x, b: int, dtype) -> jax.Array:
    """Broadcast a scalar-or-per-lane operand to a strong-typed [b]
    vector (one dtype/shape signature per batch size => no retraces)."""
    a = np.broadcast_to(np.asarray(x, dtype), (b,))
    return jnp.asarray(np.ascontiguousarray(a))


def _shard_devices(shard) -> int:
    """Normalize a ``shard`` argument (bool or device count) to the
    number of mesh devices to span: ``False``/``0`` → 1 (plain vmap),
    ``True`` → every local device, an int → that many (clamped to what
    the host actually has, never below 1)."""
    if shard is True:
        return jax.local_device_count()
    if not shard:
        return 1
    return max(1, min(int(shard), jax.local_device_count()))


def _align_up(n: int, d: int) -> int:
    """``n`` rounded up to a multiple of ``d``."""
    return -(-int(n) // int(d)) * int(d)


def _horizons(until, max_epochs, b: int) -> tuple[np.ndarray, np.ndarray]:
    """Normalize scalar-or-per-lane horizons to host vectors: [b] f32
    ``until`` and [b] i32 ``max_epochs`` (budgets beyond int32 clamp —
    the engine's epoch counter is i32, so the clamp is exact)."""
    u = np.broadcast_to(np.asarray(until, np.float32), (b,)) \
        .astype(np.float32)
    m = np.broadcast_to(
        np.minimum(np.asarray(max_epochs, np.int64), INT32_MAX)
        .astype(np.int32), (b,)).astype(np.int32)
    return u, m


class BatchRunner:
    """Compiled batched runs over one :class:`Simulation`'s design space.

    Jitted executables are cached per (batch size, shard topology) — the
    horizon and epoch budget are traced per-lane operands, so neither
    ``until`` nor ``max_epochs`` keys the cache and chunk-ladder rounds
    never recompile after warmup.  ``trace_count`` counts actual
    retraces (each jit compile runs the wrapped python once) and is
    pinned by ``tests/dse/test_rounds.py``.
    """

    def __init__(self, sim):
        self.sim = sim
        self._fns: dict[tuple, Callable] = {}
        self.trace_count = 0          # python re-traces == XLA compiles
        # devices -> autotuned rung: the winning chunk depends on the
        # shard topology (per-device width is C/d), so a runner reused
        # under a different mesh must not inherit a stale rung
        self._tuned_top: dict[int, int] = {}
        self.last_rounds: dict | None = None    # diagnostics of last run
        self.last_shard = 1           # devices the last run_batch spanned

    # ------------------------------------------------------------------
    def _batched_fn(self, b: int, d: int):
        """The compiled batched run for batch size ``b`` spanning ``d``
        mesh devices.  ``d == 1`` is the plain jitted vmap; ``d > 1``
        wraps the same vmap in ``shard_map`` over the shared lane mesh
        (``core.pdes.lane_mesh``) — lanes lay out as ``[d, b/d]``, one
        executable runs across the whole mesh, and because lanes are
        independent under vmap the rows are bit-identical to the
        single-device path.  ``b`` must be a multiple of ``d`` (callers
        pad with zero-horizon lanes — see :meth:`run_batch`)."""
        key = (b, d)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        sim = self.sim
        # Whole executables persist for one device only: an AOT
        # executable takes only the input layout it was compiled for,
        # and round assembly hands a sharded rung whatever layout its
        # gathers produced (e.g. replicated).  Sharded rungs still skip
        # compiles through the JAX compilation cache.
        persist = dse_cache.active() and d == 1
        if persist:
            # whole-executable rehydrate: the persisted binary skips
            # trace + lower + compile entirely (bit-identical results —
            # it IS the executable a fresh compile would produce)
            loaded = dse_cache.get_executable(sim, b, d)
            if loaded is not None:
                self._fns[key] = loaded
                return loaded

        def one(s, p, u, m):
            self.trace_count += 1     # runs only while (re)tracing
            return sim._run(s, u, m, params=p)

        vm = jax.vmap(one, in_axes=(0, 0, 0, 0))
        if d > 1:
            assert b % d == 0, (b, d)
            # one program over the whole mesh: each device traces the
            # same vmap over its b/d local lanes (SPMD — the DSE config
            # axis is embarrassingly parallel, so no collectives)
            sm = jax.shard_map(
                vm, mesh=lane_mesh(d),
                in_specs=(P(LANE_AXIS),) * 4, out_specs=P(LANE_AXIS),
                check_vma=False)
            fn = jax.jit(sm, donate_argnums=(0,) if sim.donate else ())
        else:
            fn = jax.jit(
                vm, donate_argnums=(0,) if sim.donate else ())
        if persist:
            target, runner = fn, self

            def fn(s, p, u, m):
                # AOT on first call so the compiled object is in hand
                # to persist; lowering runs the python (same trace as
                # the lazy jit path — trace_count telemetry holds)
                hits = dse_cache.jax_cache_hits()
                compiled = target.lower(s, p, u, m).compile()
                if dse_cache.jax_cache_hits() == hits:   # a real compile
                    dse_cache.put_executable(sim, b, d, compiled)
                runner._fns[key] = compiled
                return compiled(s, p, u, m)
        self._fns[key] = fn
        return fn

    def _liveness_start(self, out_b: SimState, u_vec, budget_vec):
        """Dispatch the per-lane ``(live, epochs)`` liveness program on a
        batched state and *start* its device→host copy asynchronously
        (``copy_to_host_async``) — the round loop's non-blocking half.
        ``live`` means the lane still has events before its horizon and
        epoch budget — the compaction key.  Returns an opaque pending
        handle for :meth:`_liveness_read`; nothing here blocks on the
        device, so the caller can keep dispatching (the next round's
        step) while the transfer drains in the background."""
        b = int(out_b.time.shape[0])
        key = ("live", b)
        fn = self._fns.get(key)
        if fn is None:
            sim = self.sim

            def one(s, u, m):
                self.trace_count += 1
                return sim._live(s, u, m), s.stats.epochs

            fn = jax.jit(jax.vmap(one))
            self._fns[key] = fn
        tc0 = self.trace_count
        t0 = time.perf_counter()
        live, ep = fn(out_b, _vec(u_vec, b, np.float32),
                      _vec(budget_vec, b, np.int32))
        if BUS.active and self.trace_count > tc0:
            BUS.emit("compile", what="liveness", b=b,
                     n=self.trace_count - tc0,
                     dur=time.perf_counter() - t0)
        for a in (live, ep):
            a.copy_to_host_async()
        return (live, ep, b)

    def _liveness_read(self, pending):
        """Blocking half of the liveness pull: materialize the vectors a
        :meth:`_liveness_start` call put in flight.  Returns
        ``((live, epochs), wait_s)`` — ``wait_s`` is the time spent
        blocked here, which under pipelining is (near) zero because the
        transfer ran while the host did round *k+1*'s work."""
        live, ep, b = pending
        t0 = time.perf_counter()
        out = jax.device_get((live, ep))
        dt = time.perf_counter() - t0
        if BUS.active:
            BUS.emit("transfer", what="liveness", b=b, dur=dt)
            BUS.observe("dse.transfer.liveness_s", dt)
        return out, dt

    def _liveness(self, out_b: SimState, u_vec, budget_vec):
        """Dispatch + block: the one-shot liveness pull (warm-ladder and
        compatibility callers; the round loop uses the split halves)."""
        out, _ = self._liveness_read(
            self._liveness_start(out_b, u_vec, budget_vec))
        return out

    # ------------------------------------------------------------------
    def run_batch(self, states_b: SimState, params_b: SimParams,
                  until, max_epochs=2_000_000,
                  shard: "bool | int" = False) -> SimState:
        """One vmapped jitted run of a pre-stacked batch.

        ``until`` and ``max_epochs`` may be scalars (shared by every
        lane) or per-lane vectors of length B — each lane freezes
        bit-exactly at its own horizon / budget (stragglers excepted,
        the loop still *iterates* until the slowest lane is done; use
        :meth:`run_rounds` to reclaim that waste).

        ``shard`` spans the lane mesh: ``True`` means every local
        device, an int pins the count.  A batch that doesn't divide the
        device count is padded to the next multiple by repeating the
        last lane at **zero horizon and zero budget** (it freezes on
        entry, exactly like chunk padding) and the padding rows are
        sliced off the result — every device runs ``ceil(B/d)`` lanes
        instead of silently falling back to a divisor of B.

        ``states_b`` is donated when the simulation was built with
        ``donate=True`` — treat it as consumed (see ``stack_states`` /
        ``Simulation.copy_state``); reusing a consumed batch raises
        immediately instead of failing deep inside XLA dispatch.
        """
        if self.sim.donate:
            check_not_consumed(states_b)
        b = int(params_b.conn_latency.shape[0])
        d = _shard_devices(shard)
        self.last_shard = d
        u, m = _horizons(until, max_epochs, b)
        pad = _align_up(b, d) - b
        if pad:
            grow = lambda x: jnp.concatenate([x] + [x[-1:]] * pad)
            states_b = jax.tree.map(grow, states_b)
            params_b = jax.tree.map(grow, params_b)
            u = np.concatenate([u, np.zeros(pad, np.float32)])
            m = np.concatenate([m, np.zeros(pad, np.int32)])
        fn = self._batched_fn(b + pad, d)
        trim = (lambda o: jax.tree.map(lambda x: x[:b], o)) if pad \
            else (lambda o: o)
        if not BUS.active:
            return trim(fn(states_b, params_b, jnp.asarray(u),
                           jnp.asarray(m)))
        # telemetry: a trace_count bump across this (host-side) dispatch
        # means XLA traced+compiled a fresh executable inside the call
        tc0 = self.trace_count
        t0 = time.perf_counter()
        out = fn(states_b, params_b, jnp.asarray(u), jnp.asarray(m))
        if self.trace_count > tc0:
            BUS.emit("compile", what="run", b=b + pad, shard=d,
                     n=self.trace_count - tc0,
                     dur=time.perf_counter() - t0)
            BUS.count("dse.compiles", self.trace_count - tc0)
        return trim(out)

    # ------------------------------------------------------------------
    def run_chunked(self, template: SimState | Sequence[SimState],
                    params_b: SimParams, until,
                    chunk: int | None = None,
                    max_epochs=2_000_000,
                    shard: "bool | int" = False) -> SimState:
        """Run a B-point batch in fixed-size chunks of fresh state stacks.

        ``template`` is either one ``SimState`` (every lane starts from a
        fresh copy of it) or a sequence of B per-lane states (topology
        families: each lane's initial state encodes its sub-shape's
        workload).  ``until`` / ``max_epochs`` may be per-lane vectors.
        All chunks share one compiled executable; the final partial chunk
        is padded by repeating its last point with a **zero horizon and
        zero epoch budget** — padding lanes freeze on entry instead of
        re-simulating the tail point at full horizon — and the padding
        lanes are dropped from the result.  Returns the stacked final
        states in point order.
        """
        B = int(params_b.conn_latency.shape[0])
        per_lane = isinstance(template, (list, tuple))
        if per_lane:
            assert len(template) == B, (len(template), B)
        u, m = _horizons(until, max_epochs, B)
        chunk = B if chunk is None else max(1, min(int(chunk), B))
        outs = []
        for lo in range(0, B, chunk):
            hi = min(lo + chunk, B)
            part = jax.tree.map(lambda x: x[lo:hi], params_b)
            pad = chunk - (hi - lo)
            u_p, m_p = u[lo:hi], m[lo:hi]
            if pad:                   # repeat the last point's row shape,
                part = jax.tree.map(  # but freeze it: until=0, budget=0
                    lambda x: jnp.concatenate([x] + [x[-1:]] * pad), part)
                u_p = np.concatenate([u_p, np.zeros(pad, np.float32)])
                m_p = np.concatenate([m_p, np.zeros(pad, np.int32)])
            if per_lane:
                lanes = list(template[lo:hi])
                lanes += [lanes[-1]] * pad
                sb = stack_state_list(lanes)
            else:
                sb = stack_states(template, chunk)
            out = self.run_batch(sb, part, u_p, m_p, shard)
            if pad:
                out = jax.tree.map(lambda x: x[:hi - lo], out)
            outs.append(out)
        if len(outs) == 1:
            return outs[0]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)

    # ------------------------------------------------------------------
    def warm_ladder(self, template: SimState | Sequence[SimState],
                    params_b: SimParams, sizes: Sequence[int],
                    shard: "bool | int" = False) -> None:
        """Compile the run + liveness executables for the given batch
        sizes without advancing any lane: a zero-horizon, zero-budget
        batch traces and compiles the full program but executes no
        epochs.  Benchmarks use this so a drain-phase rung can never
        compile inside a timed region."""
        t = template[0] if isinstance(template, (list, tuple)) else template
        if self.sim.donate:
            check_not_consumed(t)
        for b in sizes:
            # host-side row replication, not a device gather: warming
            # must request exactly the executables the round loop will
            # run — an extra tiny gather program here would miss (and so
            # pollute) the persistent compilation cache on warm starts
            pb = jax.tree.map(
                lambda x: jnp.asarray(
                    np.broadcast_to(np.asarray(x)[:1],
                                    (b,) + np.shape(x)[1:])), params_b)
            out = self.run_batch(stack_states(t, b), pb, 0.0, 0, shard)
            self._liveness(out, np.zeros(b, np.float32),
                           np.zeros(b, np.int32))

    # ------------------------------------------------------------------
    def run_rounds(self, template: SimState | Sequence[SimState],
                   params_b: SimParams, until,
                   schedule: ChunkSchedule | None = None,
                   max_epochs=2_000_000,
                   shard: "bool | int" = False,
                   init_epochs=None,
                   pipeline: "bool | int | None" = None) -> SimState:
        """Straggler-free streaming run: rounds + lane compaction + the
        chunk ladder (DSE.md "Rounds and the chunk ladder").

        Each round runs one epoch *quantum* of a ladder-sized batch,
        pulls the per-lane liveness vector to host (one tiny transfer),
        records finished lanes, compacts survivors (a device gather on
        the batch axis — outside the jitted loop, so the hot loop stays
        scatter-free) and refills from the pending-config queue.  Lanes
        are independent under vmap and freeze bit-exactly at their own
        horizons, so the result is **bit-identical** to a single
        full-batch :meth:`run_batch` at per-lane ``until`` — rounds only
        change wall-clock (pinned by ``tests/dse/test_rounds.py``).

        **Pipelining** (``pipeline``, default on — ENGINE_PERF.md "Round
        pipelining"): the loop is a depth-2 software pipeline.  Round
        *k+1* is assembled from the survivor pool and the pending queue
        and its device step + liveness program are *dispatched* before
        the host blocks on round *k*'s liveness — whose device→host
        copy was already started asynchronously at dispatch time
        (:meth:`_liveness_start`) — so device compute and host-side
        harvest/compact/refill overlap instead of alternating.  The two
        in-flight rounds are disjoint lane sets in independent
        donation-safe buffers (assembly always materializes fresh
        buffers), rotated every round; because lanes are independent and
        freeze bit-exactly at their own horizons, *which* round a lane
        rides in never changes its result — pipelined rows are
        bit-identical to the sequential loop's (pinned by
        ``tests/dse/test_pipeline.py``).  The host only synchronizes on
        a round when deciding its compaction — never to choose the next
        dispatch's executable shape, which is sized from the lanes
        already resolved.  ``pipeline=False`` (or ``1``) restores the
        strictly-alternating loop; an int sets the depth explicitly.
        Autotune probe rounds and the endgame run unpipelined (probes
        need clean per-round timings; the endgame needs every lane
        resolved).

        Under ``shard`` the round batch spans the lane mesh as
        ``[d, C/d]`` and the compact/refill step is **global**: the
        survivor pool is one host-side queue across all shards, so each
        round re-packs live lanes over the whole mesh and a shard whose
        lanes drained early picks up its neighbours' pending work
        instead of idling (the per-round ``shard.rebalance`` event
        counts lanes that changed shard).  Ladder rungs align up to
        multiples of ``d`` so every device runs the same lane count.

        ``schedule`` defaults to :func:`~repro.dse.schedule.auto_schedule`
        — with a one-shot chunk autotune for large B whose winning rung
        is cached on this runner (and, when a campaign cache dir is
        configured, persisted via ``repro.dse.cache`` keyed on the sim
        signature + shard topology, so a *fresh process* also skips the
        probe and asks for exactly the executables a previous process
        compiled).  Returns the stacked final states in point order.

        ``init_epochs`` (scalar or per-lane) is the epoch count already
        recorded in each lane's *initial* state — warm resumes pass the
        epochs a :class:`ResumeHandle` carries so the very first round's
        quantum cap advances from there instead of from zero (a cap
        below the state's own counter would execute an empty round; the
        liveness pull self-corrects, but only after a wasted dispatch).
        """
        B = int(params_b.conn_latency.shape[0])
        per_lane = isinstance(template, (list, tuple))
        if per_lane:
            assert len(template) == B, (len(template), B)
        if self.sim.donate:      # catch consumed templates up front, not
            for t in (template if per_lane else [template]):  # mid-round
                check_not_consumed(t)
        u, budget = _horizons(until, max_epochs, B)
        d = _shard_devices(shard)
        auto = schedule is None
        schedule = auto_schedule(B) if auto else \
            dataclasses.replace(schedule)              # never mutate input
        if d > 1:
            # align every rung up to a multiple of d — each round's batch
            # lays out as [d, C/d], and an unaligned rung would pad every
            # round; tuner/ladder bookkeeping all works in aligned units
            schedule = dataclasses.replace(
                schedule, ladder=tuple(sorted(
                    {_align_up(r, d) for r in schedule.ladder},
                    reverse=True)))
        if auto:
            tuned = self._tuned_top.get(d)
            if tuned is None:
                tuned = dse_cache.get_tuned_top(self.sim, d)
                if tuned is not None:   # a previous process's winner
                    self._tuned_top[d] = tuned
            if tuned is not None:
                schedule = schedule.narrowed(tuned)
        # with a persistent compilation cache, pre-warm the rungs a
        # previous process used for this (sim, B, topology): compiles
        # deserialize from disk in milliseconds instead of stalling the
        # first rounds, and the endgame rung can never compile mid-drain
        if dse_cache.active():
            known = dse_cache.get_rung_set(self.sim, B, d) or []
            cold = [r for r in known if (r, d) not in self._fns]
            if cold:
                self.warm_ladder(template, params_b, cold, shard=d)

        depth = (2 if pipeline is None or pipeline is True else
                 1 if pipeline is False else max(1, int(pipeline)))

        ep = np.broadcast_to(               # per-lane epochs so far
            np.asarray(0 if init_epochs is None else init_epochs,
                       np.int64), (B,)).copy()
        done: list[tuple[list[int], SimState]] = []   # finished segments
        pending = list(range(B))            # configs not yet started
        pool: list[tuple[list[int], SimState]] = []   # alive, unscheduled
        tuner = (ChunkAutotuner(schedule, len(pending))
                 if schedule.autotune else None)
        pad_template = template[0] if per_lane else template
        n_rounds = 0
        n_dispatched = 0
        host_accum = wait_accum = 0.0
        used_rungs: set[int] = set()
        shard_of: dict[int, int] = {}   # config -> mesh slot last round
        if BUS.active:
            BUS.emit("rounds.start", B=B, per_lane=per_lane,
                     ladder=list(schedule.ladder),
                     quantum=schedule.quantum, shard=d,
                     autotune=bool(schedule.autotune), pipeline=depth)

        def fresh(ids):
            if per_lane:
                return stack_state_list([template[i] for i in ids])
            return stack_states(template, len(ids))

        # two in-flight rounds, resolved FIFO; each entry is a dispatched
        # round whose liveness copy is already streaming to host
        inflight: "collections.deque" = collections.deque()

        def dispatch():
            """Assemble one round from the pool + pending queue
            (``round.assemble``) and enqueue its device step and async
            liveness pull (``round.launch``).  Pure host and dispatch
            work — never blocks on the device, so it runs concurrently
            with the previous round's compute.  Only the assembly counts
            toward the round's ``host_s``: the launch (``run_batch`` and
            the liveness start) is in neither ``host_s`` nor ``wait_s``."""
            nonlocal tuner, schedule, pending, n_dispatched
            h0 = time.perf_counter()
            with BUS.span("round.assemble"):
                n_alive = sum(len(ids) for ids, _ in pool)
                remaining = n_alive + len(pending)
                rung = None
                if tuner is not None:
                    rung = tuner.next_probe(remaining)
                    if rung is None:              # probing done: pick winner
                        top = tuner.best(schedule.top)
                        if BUS.active:
                            BUS.emit("autotune.winner", top=top,
                                     rates={str(r): rate for r, rate
                                            in tuner.rates.items()})
                        schedule = schedule.narrowed(top)
                        self._tuned_top[d] = top
                        dse_cache.put_tuned_top(self.sim, d, top)
                        tuner = None
                C = rung if rung is not None else schedule.size_for(remaining)
                # Endgame: once everything left fits the smallest rung there
                # is nothing to compact *into* and no queue to refill from —
                # quantum rounds would be pure overhead, so run to the full
                # budget in one round (this is also the whole story for
                # B <= the smallest rung: one round, monolithic-equivalent).
                # Needs *every* lane resolved, so only when nothing is in
                # flight (in-flight survivors may still need this rung).
                endgame = (tuner is None and not inflight
                           and remaining <= schedule.ladder[-1])

                # --- assemble the round's batch: survivors, refill, pad ----
                parts, ids = [], []
                room = C
                while pool and room:
                    seg_ids, seg = pool[0]
                    if len(seg_ids) <= room:
                        pool.pop(0)
                        parts.append(seg)
                        ids += seg_ids
                        room -= len(seg_ids)
                    else:                 # split a segment across rounds
                        cut = np.arange(len(seg_ids), dtype=np.int32)
                        parts.append(_take(seg, cut[:room]))
                        pool[0] = (seg_ids[room:], _take(seg, cut[room:]))
                        ids += seg_ids[:room]
                        room = 0
                n_fresh = min(room, len(pending))
                spawned: list[int] = []
                if n_fresh:
                    take, pending = pending[:n_fresh], pending[n_fresh:]
                    parts.append(fresh(take))
                    ids += take
                    spawned = take
                    room -= n_fresh
                if room:                  # zero-horizon padding: freezes on
                    parts.append(stack_states(pad_template, room))  # entry
                    ids += [-1] * room
                sb = parts[0] if len(parts) == 1 else _cat(parts)

                rows = np.asarray(ids, np.int32)
                live_row = rows >= 0
                ridx = np.where(live_row, rows, 0)
                if C == B and np.array_equal(ridx, np.arange(B)):
                    pb = params_b         # identity round: skip the gather
                else:
                    pb = _take(params_b, ridx)
                u_vec = np.where(live_row, u[ridx], 0.0).astype(np.float32)
                cap = budget[ridx].astype(np.int64) if endgame else \
                    np.minimum(ep[ridx] + schedule.quantum,
                               budget[ridx].astype(np.int64))
                m_vec = np.where(live_row, cap, 0).astype(np.int32)
                b_vec = np.where(live_row, budget[ridx], 0).astype(np.int32)

                used_rungs.add(C)
                tele = BUS.active         # snapshot once per round
                if tele and d > 1:
                    # global re-pack diagnostics: which mesh slot does each
                    # live config land on this round, vs where it ran last
                    # round — moved lanes are exactly the cross-shard
                    # rebalancing the pmap path couldn't do
                    per_dev = C // d
                    moved = n_live = 0
                    for j, i in enumerate(ids):
                        if i < 0:
                            continue
                        n_live += 1
                        slot = j // per_dev
                        if i in shard_of and shard_of[i] != slot:
                            moved += 1
                        shard_of[i] = slot
                    BUS.emit("shard.rebalance", round=n_dispatched, shards=d,
                             moved=moved, lanes=n_live)
                    BUS.count("dse.shard.lanes_moved", moved)
            t0 = time.perf_counter()
            with BUS.span("round.launch"):
                out = self.run_batch(sb, pb, u_vec, m_vec, d)
                pend = self._liveness_start(out, u_vec, b_vec)
            n_dispatched += 1
            return {"ids": ids, "out": out, "pend": pend, "C": C,
                    "rung": rung, "endgame": endgame,
                    "live_row": live_row, "spawned": spawned,
                    "round": n_dispatched - 1,
                    "t_dispatch": t0, "host_s": t0 - h0}

        def resolve(rec):
            """Block on a dispatched round's liveness (``round.wait``,
            the round's ``wait_s``; the copy has been streaming since
            dispatch), then harvest finished lanes and compact survivors
            back into the pool (``round.harvest``, which with the
            assembly makes the round's ``host_s``)."""
            nonlocal n_rounds, host_accum, wait_accum
            with BUS.span("round.wait"):
                (live, ep_c), wait_s = self._liveness_read(rec["pend"])
            dt = time.perf_counter() - rec["t_dispatch"]
            h0 = time.perf_counter()
            with BUS.span("round.harvest"):
                ids, out, C = rec["ids"], rec["out"], rec["C"]
                live_row, spawned = rec["live_row"], rec["spawned"]
                tele = BUS.active

                round_epochs = 0
                surv_rows, surv_ids = [], []
                fin_rows, fin_ids = [], []
                for j, i in enumerate(ids):
                    if i < 0:
                        continue
                    if tele:
                        round_epochs += int(ep_c[j]) - int(ep[i])
                    ep[i] = int(ep_c[j])
                    if live[j]:
                        surv_rows.append(j)
                        surv_ids.append(i)
                    else:
                        fin_rows.append(j)
                        fin_ids.append(i)
                # compaction / harvest: one gather per leaf per group (lane
                # slicing per config would be ~leaves x lanes dispatches);
                # a round the whole batch finishes (or survives) needs none
                if fin_rows:
                    if len(fin_rows) == C:
                        done.append((fin_ids, out))
                    else:
                        done.append((fin_ids, _take(
                            out, np.asarray(fin_rows, np.int32))))
                if surv_rows:
                    if len(surv_rows) == C:
                        pool.append((surv_ids, out))
                    else:
                        pool.append((surv_ids, _take(
                            out, np.asarray(surv_rows, np.int32))))
                host_s = rec["host_s"] + (time.perf_counter() - h0)
                host_accum += host_s
                wait_accum += wait_s
                if tuner is not None:
                    tuner.record(C, dt, lanes=int(np.sum(live_row)),
                                 host_dt=host_s)
                    if tele and C in tuner.rates:
                        BUS.emit("autotune.probe", rung=C, dur=dt,
                                 lanes=int(np.sum(live_row)),
                                 rate=tuner.rates[C])
                else:
                    q0 = schedule.quantum
                    schedule.grow_quantum(dt, host_s, steps=depth)
                    if tele and schedule.quantum != q0:
                        BUS.emit("quantum.grow", quantum=schedule.quantum,
                                 was=q0, round_dur=dt, host_s=host_s)
                if tele:
                    # the per-round heartbeat: lane spawn/freeze/harvest and
                    # the compaction decision, one event per drained round
                    overlap = host_s / max(host_s + wait_s, 1e-9)
                    BUS.emit(
                        "round.end", round=rec["round"], rung=C, dur=dt,
                        live=int(np.sum(live_row)), fresh=len(spawned),
                        pad=int(np.sum(~live_row)), epochs=round_epochs,
                        finished=len(fin_ids), survivors=len(surv_ids),
                        pending=len(pending),
                        pool=sum(len(g) for g, _ in pool),
                        quantum=schedule.quantum,
                        endgame=bool(rec["endgame"]),
                        probe=rec["rung"] is not None,
                        compacted=bool(surv_rows)
                        and len(surv_rows) != C,
                        inflight=len(inflight),
                        host_s=host_s, wait_s=wait_s,
                        overlap_frac=overlap,
                        spawned_ids=spawned[:128],
                        frozen_ids=fin_ids[:128])
                    BUS.count("dse.rounds")
                    BUS.count("dse.lanes_finished", len(fin_ids))
                    BUS.observe("dse.round_s", dt)
                    BUS.gauge("dse.lanes_live", len(surv_ids))
                    BUS.gauge("dse.lanes_pending", len(pending))
                    BUS.gauge("dse.round.overlap_frac", overlap)
                n_rounds += 1

        while pool or pending or inflight:
            # fill the pipeline: dispatch up to ``depth`` rounds before
            # blocking on the oldest round's liveness — round k+1's
            # assembly/dispatch overlaps round k's device compute.
            # Probe rounds stay unpipelined (they need clean per-round
            # timings) and the endgame is terminal by construction.
            while (pool or pending) and len(inflight) < depth:
                inflight.append(dispatch())
                if inflight[-1]["endgame"] or tuner is not None:
                    break
            resolve(inflight.popleft())

        occ = host_accum / max(host_accum + wait_accum, 1e-9)
        self.last_rounds = {"rounds": n_rounds, "chunk": schedule.top,
                            "quantum": schedule.quantum, "shard": d,
                            "pipeline": depth,
                            "host_s": host_accum, "wait_s": wait_accum,
                            "overlap_frac": occ,
                            "trace_count": self.trace_count}
        # remember which rungs this (sim, B, topology) actually compiled
        # so the next process can pre-warm them from the persistent cache
        dse_cache.put_rung_set(self.sim, B, d, used_rungs)
        if BUS.active:
            BUS.emit("rounds.end", B=B, rounds=n_rounds,
                     chunk=schedule.top, quantum=schedule.quantum,
                     shard=d, pipeline=depth, overlap_frac=occ,
                     trace_count=self.trace_count)
        with BUS.span("rounds.final"):
            # final assembly in point order: concat the finished segments
            # once, then one gather per leaf restores lane order
            all_ids = np.asarray([i for ids, _ in done for i in ids], np.int32)
            full = (done[0][1] if len(done) == 1 else
                    _cat([t for _, t in done]))
            if np.array_equal(all_ids, np.arange(B)):
                return full               # already in point order
            pos = np.empty(B, np.int32)
            pos[all_ids] = np.arange(B, dtype=np.int32)
            return _take(full, pos)


# ---------------------------------------------------------------------------
_RUNNERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def runner_for(sim) -> BatchRunner:
    """The shared :class:`BatchRunner` of a simulation (weak-keyed, so
    dropping the sim drops its runner and executables).

    ``run_sweep`` uses this instead of a private runner per call: when a
    build function memoizes and returns the *same* ``Simulation`` again,
    repeat sweeps reuse its compiled rungs and autotuned chunk instead
    of re-jitting and re-probing (a build function that rebuilds per
    call compiles fresh either way — structure is the compile key).
    """
    r = _RUNNERS.get(sim)
    if r is None:
        r = _RUNNERS[sim] = BatchRunner(sim)
    return r


def memoize_build(build_fn: Callable) -> Callable:
    """Memoize a sweep build function across calls, so incremental point
    submission (search rounds, repeated sweeps) reuses one built
    simulation — and therefore :func:`runner_for`'s compiled rungs and
    autotuned chunk — instead of rebuilding and recompiling per round.

    * Plain groups: the ``(sim, state)`` of each distinct ``static.*``
      kwarg combination is cached and returned as-is (``run_sweep``
      copies the template state per lane, so it is never consumed).
    * Topology families (``shape=`` calls): the cached family is reused
      whenever its ``shape_max`` covers the requested shape — a search
      round asking for a *smaller* maximum (survivors shrank) runs as
      masked lanes of the already-compiled family.  A request that
      exceeds the cache is rebuilt at the elementwise maximum of old and
      new, so repeated growth converges to one family per group.  When a
      campaign cache dir is configured (``repro.dse.cache``) the union
      also persists *across processes*, keyed on the build function +
      static kwargs: a fresh process builds the family at the previous
      process's final maximum in one shot, so its executable shapes
      match the persistent compilation cache exactly instead of
      re-walking the growth sequence.

    The wrapper forwards ``build_fn``'s signature (``functools.wraps``),
    so ``run_sweep``'s eager ``static.*`` kwarg validation still sees
    the real keyword names.  Idempotent to re-wrap; keep the wrapper
    itself alive to keep the cache (and the weak-keyed runners) alive.
    """
    if getattr(build_fn, "_dse_memoized", False):
        return build_fn
    cache: dict[tuple, object] = {}

    @functools.wraps(build_fn)
    def wrapped(*args, **kw):
        shape = kw.pop("shape", None)
        # family and plain builds of the same static kwargs return
        # different objects — keep them in disjoint cache slots
        key = (shape is not None, args, tuple(sorted(kw.items())))
        if shape is None:
            if key not in cache:
                cache[key] = build_fn(*args, **kw)
            return cache[key]
        fam = cache.get(key)
        if fam is not None and all(
                fam.shape_max.get(a, 0) >= int(v)
                for a, v in shape.items()):
            return fam
        grown = dict(shape)
        if fam is not None:
            for a, v in fam.shape_max.items():
                grown[a] = max(int(grown.get(a, 0)), int(v))
        bkey = None
        if dse_cache.active():        # cross-process union (same axes only
            bkey = dse_cache.family_build_key(build_fn, args, kw)
            persisted = dse_cache.get_family_shape(bkey)
            if persisted:             # — a foreign axis would leak into
                for a, v in persisted.items():   # the build signature)
                    if a in grown:
                        grown[a] = max(int(grown[a]), int(v))
        fam = build_fn(*args, **kw, shape=grown)
        cache[key] = fam
        if bkey is not None:
            dse_cache.put_family_shape(bkey, fam.shape_max)
        return fam

    wrapped._dse_memoized = True
    return wrapped


def _static_kwarg_names(build_fn) -> list[str] | None:
    """Keyword names ``build_fn`` accepts, or None if it takes **kwargs
    (then any ``static.*`` axis must be assumed valid)."""
    try:
        sig = inspect.signature(build_fn)
    except (TypeError, ValueError):
        return None
    params = sig.parameters.values()
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        return None
    return [p.name for p in params
            if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                          inspect.Parameter.KEYWORD_ONLY)]


def _extract_arity(fn) -> int:
    """2 for the classic ``extract(sim, lane_state)`` signature, 3 when
    the extractor also wants the point's global index (``extract(sim,
    lane_state, index)`` — what :class:`~repro.dse.mux.LaneMux` uses to
    route rows back to their owning job)."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return 2
    n = 0
    for p in sig.parameters.values():
        if p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                      inspect.Parameter.POSITIONAL_OR_KEYWORD):
            n += 1
        elif p.kind is inspect.Parameter.VAR_POSITIONAL:
            return 3
    return 3 if n >= 3 else 2


def run_sweep(build_fn: Callable, spec: SweepSpec, until,
              extract: Callable | None = None, chunk: int | None = None,
              max_epochs: "int | Sequence[int]" = 2_000_000,
              shard: "bool | int" = False,
              schedule: ChunkSchedule | None = None,
              resume: Sequence[ResumeHandle | None] | None = None,
              return_states: bool = False,
              pipeline: "bool | int | None" = None):
    """Simulate every design point of ``spec`` and return tidy result rows.

    ``build_fn(**static_kwargs) -> (sim, state)`` builds the topology; it
    is called once per distinct ``static.*`` axis combination (each such
    group compiles once and vmaps its traced points).  ``extract(sim,
    final_lane_state) -> dict`` pulls per-config results (default: engine
    counters); lanes are handed to it *host-side* — one ``jax.device_get``
    per chunk — so scalar casts in the extractor never sync.  An extractor
    that takes a third positional arg gets the point's global spec index
    too (``extract(sim, lane_state, index)`` — how
    :class:`~repro.dse.mux.LaneMux` routes rows of interleaved jobs).
    Rows come back in spec order, each the point's axis assignment merged
    with its extracted results.

    Execution is **round-based and straggler-free**
    (:meth:`BatchRunner.run_rounds`): every group streams through the
    chunk ladder with per-lane horizons, lane compaction and pending-
    queue refill, so arbitrary B runs through a handful of cached
    executables with zero recompiles after warmup.  ``chunk`` pins the
    ladder's top rung (otherwise large groups autotune it); ``schedule``
    overrides the whole policy.  ``until`` may be a scalar or a per-point
    sequence (mixed horizons — e.g. successive-halving search rounds).
    ``shard=True`` (or a device count) spans each round over the lane
    mesh with globally-rebalanced compaction — rows stay bit-identical
    to the single-device path (:meth:`BatchRunner.run_rounds`).
    ``pipeline`` forwards to :meth:`BatchRunner.run_rounds` — rounds
    pipeline at depth 2 by default (host compaction overlaps device
    compute); ``pipeline=False`` restores the alternating loop,
    bit-identically.

    **Topology families** (``shape.*`` axes, DSE.md): shape axes sweep
    instance counts / wiring *without* forming compile groups.  The
    runner groups by ``static.*`` only, computes each group's family
    maximum per shape axis, and calls ``build_fn(**static_kwargs,
    shape={axis: max})``, which must return a
    :class:`~repro.dse.family.TopologyFamily`.  Every shape in the group
    then runs as lanes of the same ladder rungs — activity masks and
    per-lane initial states select each sub-shape, and masked lanes
    compose with per-lane horizons (a masked lane's next-event min
    simply reaches its horizon earlier).

    All axis paths are validated before anything runs: unknown axes
    raise ``ValueError`` naming the path and the valid alternatives.

    **Warm resume** (``resume=``): a per-point sequence of
    :class:`ResumeHandle` / ``None``.  A handled point's lane starts
    from the handle's frozen final state instead of a fresh template
    copy and simply runs on to its (longer, absolute) ``until`` — the
    engine's epoch sequence is state-determined, so the result row is
    bit-identical to a cold run at that horizon while only the cycles
    *since the handle* are newly simulated.  ``return_states=True``
    returns ``(rows, LaneStates)`` — lazy per-point final states (from
    the same host transfer the rows use) that a search can package into
    next-rung handles.
    """
    if chunk is not None and schedule is not None:
        raise ValueError(
            "pass either chunk= (pins the ladder top) or schedule= (the "
            "whole policy), not both — a schedule carries its own ladder")
    if resume is not None and len(resume) != len(spec):
        raise ValueError(
            f"resume= must give one handle (or None) per point: "
            f"{len(resume)} != {len(spec)}")
    with BUS.span("sweep"):
        dse_cache.ensure_enabled()       # enable-on-first-sweep: wire the
        # persistent jax compilation cache when a campaign dir is configured
        rows: list[dict | None] = [None] * len(spec)
        lane_states = LaneStates() if return_states else None
        until_arr = np.broadcast_to(np.asarray(until, np.float32),
                                    (len(spec),))
        me_arr = np.broadcast_to(np.asarray(max_epochs, np.int64),
                                 (len(spec),))
        shape_mode = spec.has_shape_axes()
        tele = BUS.active
        sweep_t0 = time.perf_counter()
        if tele:
            BUS.emit("sweep.start", n_points=len(spec), axes=spec.summary(),
                     shape_mode=bool(shape_mode), shard=_shard_devices(shard),
                     warm=(0 if resume is None
                           else sum(1 for h in resume if h is not None)))
            BUS.count("dse.sweeps")
        static_ok = _static_kwarg_names(build_fn)
        if static_ok is not None:
            bad = [a for a in spec.axes if a.startswith(STATIC_PREFIX)
                   and a[len(STATIC_PREFIX):] not in static_ok]
            if bad:
                raise ValueError(
                    f"invalid static axes {bad}: build function accepts "
                    f"only {sorted(static_ok)}")
        group_no = 0
        for static_kwargs, indices, traced in spec.split_static():
            if tele:
                BUS.emit("sweep.group", group=group_no,
                         static={k: str(v) for k, v in static_kwargs.items()},
                         n_points=len(indices), family=bool(shape_mode))
            group_no += 1
            # validate each group's own axes against that group's build (a
            # group's sim can differ structurally, e.g. static.n_cores, so
            # neither the whole-spec union nor a single target would do)
            group_spec = SweepSpec(tuple(traced))
            u_group = until_arr[np.asarray(indices)]
            me_group = me_arr[np.asarray(indices)]
            res = ([resume[i] for i in indices] if resume is not None
                   else None)
            warm = res is not None and any(h is not None for h in res)
            init_ep = (np.asarray([int(h.epochs) if h is not None else 0
                                   for h in res], np.int64) if warm else None)
            sched = auto_schedule(len(indices), chunk=chunk) \
                if schedule is None and chunk is not None else schedule
            if shape_mode:
                split = [split_shape(pt) for pt in traced]
                fam_shape: dict[str, int] = {}
                for shape_pt, _ in split:
                    for name, v in shape_pt.items():
                        fam_shape[name] = max(int(v), fam_shape.get(name, 1))
                with BUS.span("sweep.build"):
                    fam = build_fn(**static_kwargs, shape=fam_shape)
                    if not isinstance(fam, TopologyFamily):
                        raise TypeError(
                            "shape.* axes require a family-aware build "
                            "function: build_fn(**static, shape={...}) "
                            "must return a TopologyFamily, got "
                            f"{type(fam).__name__}")
                    group_spec.validate(fam)
                sim = fam.sim
                with BUS.span("sweep.params"):
                    base = sim.default_params()
                    # grids repeat shapes across traced-axis combinations:
                    # derive each distinct shape's masks once and share them
                    # between the lane's params and initial state
                    mask_cache: dict[tuple, tuple] = {}
                    plist, template = [], []
                    for shape_pt, traced_pt in split:
                        full = fam.full_shape(shape_pt)
                        key = tuple(sorted(full.items()))
                        if key not in mask_cache:
                            mask_cache[key] = fam.masks(full)
                        m = mask_cache[key]
                        plist.append(fam.params_for(
                            full, apply_point(base, traced_pt), masks=m))
                        template.append(fam.state_for(full, masks=m))
                    if warm:            # handled lanes continue, not restart
                        template = [h.state if h is not None else s
                                    for h, s in zip(res, template)]
                    params_b = stack_params(plist)
            else:
                with BUS.span("sweep.build"):
                    sim, st = build_fn(**static_kwargs)
                    group_spec.validate(sim)
                with BUS.span("sweep.params"):
                    params_b = build_param_batch(sim, traced)
                template = ([h.state if h is not None else st for h in res]
                            if warm else st)
            with BUS.span("sweep.rounds"):
                out = runner_for(sim).run_rounds(
                    template, params_b, u_group, schedule=sched,
                    max_epochs=me_group, shard=shard, init_epochs=init_ep,
                    pipeline=pipeline)
            # one device_get serves both the result rows and (when asked)
            # the resumable final states — never two transfers per group
            with BUS.span("sweep.transfer"):
                t0 = time.perf_counter()
                host = jax.device_get(out)
                if tele:
                    dt = time.perf_counter() - t0
                    BUS.emit("transfer", what="rows", lanes=len(indices),
                             dur=dt, bytes=int(sum(
                                 x.nbytes for x in jax.tree.leaves(host)
                                 if hasattr(x, "nbytes"))))
                    BUS.observe("dse.transfer.rows_s", dt)
            with BUS.span("sweep.extract"):
                ex = extract or default_extract
                if _extract_arity(ex) >= 3:     # index-aware: mux row routing
                    group_rows = [ex(sim, lane(host, j), indices[j])
                                  for j in range(len(indices))]
                else:
                    group_rows = [ex(sim, lane(host, j))
                                  for j in range(len(indices))]
                if lane_states is not None:
                    lane_states.add_group(host, indices)
                for j, i in enumerate(indices):
                    row = dict(spec.points[i])
                    row.update(group_rows[j])
                    rows[i] = row
        if tele:
            BUS.emit("sweep.end", n_points=len(spec), groups=group_no,
                     dur=time.perf_counter() - sweep_t0)
    if return_states:
        return list(rows), lane_states
    return list(rows)
