"""§Perf cell C: the sharded conservative-PDES engine itself.

Measures, on every visible device (at least two — with one it fails
rather than report a one-shard number as a sharded one):
  * wall time per simulated virtual cycle vs the conservative lookahead
    window (the classic PDES sync/skew trade-off: larger windows = fewer
    pmin barriers + fewer mailbox exchanges, at the cost of later message
    visibility — correctness is unaffected because inter-shard latency >=
    window);
  * cross-shard collective bytes per simulated cycle from the lowered
    512-chip artifact (the dry-run's own metric).

Run it on a multi-chip host, or on the CPU with forced host devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.run --only pdes_scaling
"""
import time

import jax
import numpy as np


def bench():
    from repro.launch.mesh import make_sim_mesh
    from repro.launch.roofline import parse_collectives
    from repro.sims.memsys import build_sharded_memsys

    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            f"pdes_scaling needs 2+ devices, found {n} "
            f"{jax.devices()[0].platform} device")
    mesh = make_sim_mesh(n)
    rows = []
    horizon = 2000.0
    # per-window wire per chip (from the 512-chip dry-run artifact):
    # 256 B collective-permute mailbox + 16 B all-reduce(min) time sync.
    wire_per_window = 272.0
    for lookahead in (4.0, 8.0, 32.0, 128.0):
        ss = build_sharded_memsys(mesh=mesh, n_shards=n, tiles_per_shard=4,
                                  n_reqs=32, lookahead=lookahead)
        st = ss.shard_state(ss.init_state())
        out, _ = ss.run(st, until=horizon, return_windows=True)  # compile
        jax.block_until_ready(out.time)
        t0 = time.perf_counter()
        out, windows = ss.run(st, until=horizon, return_windows=True)
        jax.block_until_ready(out.time)
        dt = time.perf_counter() - t0
        served = int(np.asarray(out.comp_state["dram"]["served"]).sum())
        rows.append({
            "name": f"pdes_scaling/lookahead{int(lookahead)}",
            "us_per_call": dt * 1e6,
            "derived": (f"shards={n} served={served} "
                        f"sync_rounds={windows} "
                        f"coll_bytes/cycle={wire_per_window*windows/horizon:.1f} "
                        f"wall/cycle={dt/horizon*1e6:.1f}us"),
        })
    return rows
