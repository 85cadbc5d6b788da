"""Sharded conservative PDES (paper §3.3, scaled out).

Akita parallelizes by triggering same-timestamp events on multiple CPU cores.
The JAX-native scale-out analogue: shard the *component axis* over devices
with ``shard_map``.  Each shard owns a replica of the shard-local topology
(SPMD — same compiled program, different component data) plus one ``_remote``
gateway kind whose ports are cross-shard channels.

Conservative synchronization (Fujimoto [16]; null-message-free because the
lookahead is static): all shards agree on the global next event time with
``pmin``, then each runs a *window* of ``lookahead`` cycles locally — any
message emitted inside the window arrives at its destination shard no earlier
than the window boundary plus the transport latency, so no shard can receive
a straggler event in its past.  Cross-shard messages ride fixed-capacity
mailboxes exchanged with ``all_to_all`` at window boundaries (a flow-style
network phase, the same abstraction TrioSim uses for data movement).

Component code is untouched — the same single-instance ``tick_fn`` written
for the single-device engine runs here, which is precisely the paper's
"transparent parallel simulation" claim (DX-3).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .component import ComponentKind, TickResult
from .engine import INF, SimBuilder, Simulation, _align_after
from .message import MSG_WORDS, W_DST, W_TIME, f2i
from .ports import EPS

REMOTE_KIND = "_remote"

LANE_AXIS = "lanes"          # the batched-DSE mesh axis (config lanes)

_MESHES: dict[tuple[int, str], Mesh] = {}


def lane_mesh(n_devices: int | None = None, axis: str = LANE_AXIS) -> Mesh:
    """A cached 1-D device mesh over the first ``n_devices`` local
    devices (all of them by default).

    This is the shared mesh machinery for every ``shard_map`` user in
    the repo: the PDES component-axis shards (:class:`ShardedSim`) and
    the DSE config-axis shards (``repro.dse`` sharded sweep rounds) both
    draw their meshes here, so one process holds exactly one ``Mesh``
    object per (device count, axis name) — meshes are part of jit cache
    keys, and a fresh ``Mesh`` per call would defeat executable reuse.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else max(1, min(int(n_devices),
                                                       len(devs)))
    key = (n, axis)
    m = _MESHES.get(key)
    if m is None:
        m = _MESHES[key] = Mesh(np.array(devs[:n]), (axis,))
    return m


def _gateway_tick(state, ports, t):
    # The gateway never ticks; the PDES wrapper moves its buffers directly.
    return state, ports, TickResult.make(jnp.asarray(False))


def add_gateway(builder: SimBuilder, n_peers: int, chan_per_peer: int,
                cap: int = 8) -> "object":
    """Add the cross-shard gateway kind to a shard-local topology.

    Port layout: ``port[p * 2*chan_per_peer + 2*c]`` is the *egress* channel c
    toward peer-shard-offset p (connect local senders to it), and
    ``...+ 2*c + 1`` is the matching *ingress* channel (connect it to local
    receivers).  Peer offset p means "shard (me + 1 + p) % D".
    """
    n_ports = n_peers * chan_per_peer * 2
    kind = ComponentKind(
        REMOTE_KIND, _gateway_tick, n_instances=1, n_ports=n_ports,
        init_state={"_": jnp.zeros((1,), jnp.int32)}, cap=cap,
        start_asleep=True)
    return builder.add_kind(kind)


class ShardedSim:
    """Runs one shard-local ``Simulation`` per device, conservatively synced.

    ``build_fn() -> (SimBuilder, gateway_handle)`` must register the gateway
    via :func:`add_gateway`.  All shards share the topology (SPMD); per-shard
    state is set by editing the stacked init state.
    """

    def __init__(self, build_fn, n_shards: int, n_peers: int,
                 chan_per_peer: int, mesh: Mesh | None = None,
                 axis: str = "sim", lookahead: float = 8.0,
                 mailbox: int = 8):
        builder, _ = build_fn()
        self.sim = builder.build()
        self.n_shards = n_shards
        self.n_peers, self.chan = n_peers, chan_per_peer
        self.lookahead = float(lookahead)
        self.mailbox = int(mailbox)
        self.axis = axis
        if mesh is None:
            mesh = lane_mesh(1, axis)
        self.mesh = mesh
        ki = [i for i, k in enumerate(self.sim.kinds)
              if k.name == REMOTE_KIND]
        assert ki, "topology must include the gateway (add_gateway)"
        self.gw_port_base = self.sim.port_base[ki[0]]
        assert self.sim.kinds[ki[0]].caps().max() <= self.mailbox, \
            "mailbox must cover gateway buffer capacity"

    # ------------------------------------------------------------------
    def init_state(self):
        """Stacked state [D, ...] for all shards, sharded over the mesh."""
        s0 = self.sim.init_state()
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self.n_shards,) + a.shape),
            s0)
        return stacked

    def shard_state(self, stacked):
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(self.mesh,
                                 P(*([self.axis] + [None] * (a.ndim - 1))))),
            stacked)

    # ------------------------------------------------------------------
    def _exchange(self, s, t_end):
        """Drain gateway egress -> all_to_all -> inject gateway ingress.

        With the segmented port-state layout the gateway's buffers are its
        own kind segment, so draining/injecting touches only that segment
        (no full-array scatters)."""
        sim = self.sim
        npr, ch, mb = self.n_peers, self.chan, self.mailbox
        cap = sim.cap_phys
        RK = REMOTE_KIND

        # --- drain egress in-buffers (ports 2k) into mailbox [P, C, MB, W]
        eg = np.arange(npr * ch, dtype=np.int32) * 2   # gateway-local ids
        heads, cnts = s.in_head[RK][eg], s.in_cnt[RK][eg]         # [P*C]
        idx = (heads[:, None] + jnp.arange(mb, dtype=jnp.int32)[None, :]) % cap
        msgs = s.in_buf[RK][eg[:, None], idx]                     # [P*C,MB,W]
        vmask = jnp.arange(mb)[None, :] < cnts[:, None]
        msgs = jnp.where(vmask[:, :, None], msgs, 0)
        out_mail = msgs.reshape(npr, ch, mb, MSG_WORDS)
        s = dataclasses.replace(
            s,
            in_cnt={**s.in_cnt, RK: s.in_cnt[RK].at[eg].set(0)},
            in_head={**s.in_head, RK: s.in_head[RK].at[eg].set(0)})

        # --- transport: rotate-by-offset exchange over the shard axis.
        # Peer offset p on shard i targets shard (i+1+p) % D; ppermute each
        # offset's slice (a deterministic torus schedule; for D tested up to
        # 512 via the dry-run).
        D = self.n_shards
        if D > 1:
            slabs = []
            for p in range(npr):
                perm = [(i, (i + 1 + p) % D) for i in range(D)]
                slabs.append(jax.lax.ppermute(out_mail[p], self.axis, perm))
            in_mail = jnp.stack(slabs)            # [P, C, MB, W] from peers
        else:
            in_mail = out_mail

        # --- inject into gateway ingress out-buffers (ports 2k+1)
        ing = np.arange(npr * ch, dtype=np.int32) * 2 + 1  # gateway-local
        ing_g = self.gw_port_base + ing                    # global ids
        flat = in_mail.reshape(npr * ch, mb, MSG_WORDS)
        valid = flat[:, :, 0] != 0                                 # opcode!=0
        n_new = jnp.sum(valid, axis=1).astype(jnp.int32)
        # compact valid messages to the front of each channel
        order = jnp.argsort(~valid, axis=1, stable=True)
        flat = jnp.take_along_axis(flat, order[:, :, None], axis=1)
        # rewrite dst to the ingress port's local peer; stamp ready time
        peer = sim.c["peer"][ing_g]                                # [P*C]
        flat = flat.at[:, :, W_DST].set(
            jnp.broadcast_to(peer[:, None], flat.shape[:2]))
        flat = flat.at[:, :, W_TIME].set(f2i(jnp.full(flat.shape[:2],
                                                      t_end, jnp.float32)))
        pad = jnp.zeros((npr * ch, cap - mb, MSG_WORDS), jnp.int32) \
            if cap > mb else None
        stock = jnp.concatenate([flat[:, :cap], pad], axis=1) if pad is not None \
            else flat[:, :cap]
        s = dataclasses.replace(
            s,
            out_buf={**s.out_buf, RK: s.out_buf[RK].at[ing].set(stock)},
            out_head={**s.out_head, RK: s.out_head[RK].at[ing].set(0)},
            out_cnt={**s.out_cnt,
                     RK: s.out_cnt[RK].at[ing].set(jnp.minimum(n_new, cap))})
        # wake the serving connections so the crossbar forwards them
        conns = sim.c["port_conn"][ing_g]
        has = n_new > 0
        cw = s.conn_wake.at[jnp.where(has, conns, sim.n_conn)].min(
            _align_after(t_end, 1.0), mode="drop")
        return dataclasses.replace(s, conn_wake=cw)

    # ------------------------------------------------------------------
    def _local_next(self, s):
        return jnp.minimum(jnp.min(s.next_tick), jnp.min(s.conn_wake))

    def _step_window(self, s, horizon):
        """One conservative window: sync time, run lookahead, exchange."""
        t_loc = self._local_next(s)
        t_glob = jax.lax.pmin(t_loc, self.axis)
        t_end = jnp.minimum(t_glob + self.lookahead, horizon)
        s = self.sim._run(s, t_end - 2 * EPS, max_epochs=1_000_000)
        s = dataclasses.replace(s, time=jnp.maximum(s.time, t_end))
        s = self._exchange(s, t_end)
        return s

    def run(self, stacked_state, until: float, max_windows: int = 10_000,
            return_windows: bool = False):
        """Advance all shards to virtual time ``until``."""
        spec = lambda a: P(*([self.axis] + [None] * (a.ndim - 1)))
        in_specs = jax.tree.map(spec, stacked_state)

        @partial(jax.shard_map, mesh=self.mesh, in_specs=(in_specs,),
                 out_specs=(in_specs, P(self.axis)), check_vma=False)
        def _run(st):
            s = jax.tree.map(lambda a: a[0], st)     # local shard

            def cond(carry):
                s, w = carry
                t = jax.lax.pmin(self._local_next(s), self.axis)
                return (t <= until + EPS) & (w < max_windows)

            def body(carry):
                s, w = carry
                return self._step_window(s, jnp.float32(until)), w + 1

            s, w = jax.lax.while_loop(cond, body, (s, jnp.int32(0)))
            return jax.tree.map(lambda a: a[None], s), w[None]

        out, w = _run(stacked_state)
        return (out, int(w[0])) if return_windows else out

    def lower(self, until: float = 1024.0):
        """AOT-lower ``run`` for the dry-run (no allocation)."""
        st = jax.eval_shape(self.init_state)
        fn = lambda s: self.run(s, until)
        return jax.jit(fn).lower(st)
