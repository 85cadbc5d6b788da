"""Substrate tests: data determinism/sharding, checkpoint roundtrip +
resharding + async + keep-k, optimizer correctness, gradient compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import CheckpointManager, restore_checkpoint, save_checkpoint
from repro.configs import get_smoke_config
from repro.data import DataPipeline, synthetic_batch
from repro.optim import adamw_init, adamw_update
from repro.optim.compress import dequant_int8, quant_int8


# --- data ------------------------------------------------------------------
def test_data_deterministic_and_restart_safe():
    cfg = get_smoke_config("stablelm-1.6b")
    a = synthetic_batch(cfg, 8, 32, seed=1, step=7)
    b = synthetic_batch(cfg, 8, 32, seed=1, step=7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = synthetic_batch(cfg, 8, 32, seed=1, step=8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_shards_disjoint_and_elastic():
    cfg = get_smoke_config("stablelm-1.6b")
    full = synthetic_batch(cfg, 8, 16, seed=0, step=3)
    # 2-way and 4-way shardings reconstruct the same global batch
    for world in (2, 4):
        parts = [synthetic_batch(cfg, 8, 16, seed=0, step=3, rank=r,
                                 world=world)["tokens"] for r in range(world)]
        np.testing.assert_array_equal(np.concatenate(parts), full["tokens"])


def test_text_pipeline():
    cfg = get_smoke_config("stablelm-1.6b")
    dp = DataPipeline.from_text(cfg, "hello world, " * 500, batch=4, seq=16)
    b1, b2 = dp(0), dp(0)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (4, 16)
    assert b1["tokens"].max() < cfg.vocab


# --- checkpoint --------------------------------------------------------------
def _tree():
    return {"w": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "nest": {"b": jnp.ones((5,), jnp.float32)},
            "count": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), t, step=3)
    out, manifest = restore_checkpoint(str(tmp_path), t)
    assert manifest["step"] == 3
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_keep_k_and_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    for s in range(5):
        mgr.save(_tree(), s)
    mgr.wait()
    from repro.ckpt.checkpoint import list_steps
    assert list_steps(str(tmp_path)) == [3, 4]


def test_checkpoint_reshard(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = {"w": jnp.arange(8, dtype=jnp.float32)}
    save_checkpoint(str(tmp_path), t, step=0)
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data"))}
    out, _ = restore_checkpoint(str(tmp_path), t, shardings=sh)
    assert out["w"].sharding == sh["w"]


# --- optimizer ---------------------------------------------------------------
def test_adamw_converges_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"x": jnp.zeros(3)}
    opt = adamw_init(params)
    for _ in range(300):
        g = {"x": 2 * (params["x"] - target)}
        params, opt = adamw_update(g, opt, params, lr=3e-2, weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(target),
                               atol=0.05)


def test_adamw_int8_moments_close_to_fp32():
    target = jnp.asarray([0.5, -1.5, 2.5, -3.5])
    outs = {}
    for md in ("float32", "int8"):
        params = {"x": jnp.zeros(4)}
        opt = adamw_init(params, moments_dtype=md)
        for _ in range(200):
            g = {"x": 2 * (params["x"] - target)}
            params, opt = adamw_update(g, opt, params, lr=3e-2,
                                       weight_decay=0.0, moments_dtype=md)
        outs[md] = np.asarray(params["x"])
    np.testing.assert_allclose(outs["int8"], outs["float32"], atol=0.2)


def test_int8_quant_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = quant_int8(g)
    err = np.abs(np.asarray(dequant_int8(q, s) - g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_int8_allreduce_error_feedback_converges():
    """Compressed DP training still converges on a quadratic (shard_map)."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import int8_allreduce_grads

    mesh = jax.make_mesh((1,), ("data",))
    target = jnp.asarray([1.0, -1.0])
    params = jnp.zeros(2)
    err = {"x": jnp.zeros(2)}

    for _ in range(150):
        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P(), P()),
                 out_specs=(P(), P()))
        def reduced(p, e, t):
            g = {"x": 2 * (p - t)}
            red, ne = int8_allreduce_grads(g, {"x": e}, mesh, axes=("data",))
            return red["x"], ne["x"]

        g, err_x = reduced(params, err["x"], target)
        err = {"x": err_x}
        params = params - 3e-2 * g
    np.testing.assert_allclose(np.asarray(params), np.asarray(target),
                               atol=0.05)
