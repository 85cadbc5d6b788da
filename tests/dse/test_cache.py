"""The campaign cache (``repro.dse.cache``): artifact store semantics,
key invalidation, telemetry, and the headline contract — the second
process of a campaign performs **zero** XLA compiles (every executable
deserializes from the shared persistent compilation cache).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.dse import cache as dse_cache
from repro.dse.cache import DseCache
from repro.obs.bus import capture
from repro.sims.memsys import build


@pytest.fixture()
def cache_dir(tmp_path):
    """A configured campaign cache dir, unconfigured again on exit (the
    module is process-global state)."""
    d = str(tmp_path / "campaign_cache")
    dse_cache.configure(d)
    try:
        yield d
    finally:
        dse_cache.configure(None)


# ---------------------------------------------------------------------------
# the JSON artifact store
# ---------------------------------------------------------------------------
def test_store_roundtrip_and_cross_instance_visibility(tmp_path):
    p = str(tmp_path / "store.json")
    a = DseCache(p)
    assert a.get("k") is None
    a.put("k", {"x": 1})
    assert a.get("k") == {"x": 1}
    # a second instance (= another process) sees the flushed value
    b = DseCache(p)
    assert b.get("k") == {"x": 1}
    # writes merge: b adds a key, a picks it up via the mtime check
    b.put("k2", [1, 2, 3])
    assert a.get("k2") == [1, 2, 3]
    assert a.get("k") == {"x": 1}


def test_store_survives_corrupt_file(tmp_path):
    p = str(tmp_path / "store.json")
    a = DseCache(p)
    a.put("k", 7)
    with open(p, "w") as fh:
        fh.write('{"version": 1, "entr')      # torn write
    b = DseCache(p)
    assert b.get("k") is None                  # corrupt -> miss, no raise
    b.put("k2", 8)                             # and it heals on next put
    assert DseCache(p).get("k2") == 8


def test_store_version_mismatch_is_a_miss(tmp_path):
    p = str(tmp_path / "store.json")
    with open(p, "w") as fh:
        json.dump({"version": 0, "entries": {"k": 1}}, fh)
    assert DseCache(p).get("k") is None


# ---------------------------------------------------------------------------
# keys + artifacts
# ---------------------------------------------------------------------------
def test_sim_signature_stable_and_structure_sensitive():
    sim1, _ = build(n_cores=2, n_reqs=6, donate=False)
    sim1b, _ = build(n_cores=2, n_reqs=6, donate=False)
    sim2, _ = build(n_cores=3, n_reqs=6, donate=False)
    assert dse_cache.sim_signature(sim1) == dse_cache.sim_signature(sim1b)
    assert dse_cache.sim_signature(sim1) != dse_cache.sim_signature(sim2)
    # memoized per object: repeated calls are cheap and identical
    assert dse_cache.sim_signature(sim1) == dse_cache.sim_signature(sim1)


def test_artifacts_noop_without_cache_dir():
    assert not dse_cache.active()
    sim, _ = build(n_cores=2, n_reqs=6, donate=False)
    assert dse_cache.get_tuned_top(sim, 1) is None
    dse_cache.put_tuned_top(sim, 1, 32)        # silently dropped
    assert dse_cache.get_tuned_top(sim, 1) is None


def test_tuned_top_keyed_on_sim_and_topology(cache_dir):
    sim1, _ = build(n_cores=2, n_reqs=6, donate=False)
    sim2, _ = build(n_cores=3, n_reqs=6, donate=False)
    dse_cache.put_tuned_top(sim1, 1, 32)
    dse_cache.put_tuned_top(sim1, 2, 64)
    assert dse_cache.get_tuned_top(sim1, 1) == 32
    assert dse_cache.get_tuned_top(sim1, 2) == 64   # per shard topology
    assert dse_cache.get_tuned_top(sim2, 1) is None  # per structure


def test_rung_set_union_merges(cache_dir):
    sim, _ = build(n_cores=2, n_reqs=6, donate=False)
    dse_cache.put_rung_set(sim, 64, 1, {64, 32})
    dse_cache.put_rung_set(sim, 64, 1, {32, 8})
    assert dse_cache.get_rung_set(sim, 64, 1) == [8, 32, 64]
    assert dse_cache.get_rung_set(sim, 64, 2) is None    # topology-keyed
    assert dse_cache.get_rung_set(sim, 128, 1) is None   # B-keyed


def test_family_shape_elementwise_max_merge(cache_dir):
    def bf(**kw):
        pass
    k = dse_cache.family_build_key(bf, (), {"pattern": "mixed"})
    k2 = dse_cache.family_build_key(bf, (), {"pattern": "stream"})
    assert k != k2                             # kwargs are part of the key
    dse_cache.put_family_shape(k, {"core": 2, "l1": 4})
    dse_cache.put_family_shape(k, {"core": 8, "l1": 1})
    assert dse_cache.get_family_shape(k) == {"core": 8, "l1": 4}
    assert dse_cache.get_family_shape(k2) is None


def test_cache_events_and_hit_rate_gauge(cache_dir):
    sim, _ = build(n_cores=2, n_reqs=6, donate=False)
    with capture() as sink:
        dse_cache.get_tuned_top(sim, 1)            # miss
        dse_cache.put_tuned_top(sim, 1, 16)        # write
        dse_cache.get_tuned_top(sim, 1)            # hit
    kinds = [e["kind"] for e in sink.events]
    assert kinds == ["cache.miss", "cache.write", "cache.hit"]
    hit = sink.events[-1]
    assert hit["what"] == "tuned_top" and hit["bytes"] > 0
    w = sink.events[1]
    assert w["bytes"] > 0
    from repro.obs.bus import BUS
    g = BUS.metrics.gauge("dse.cache.hit_rate").value
    assert 0.0 < g <= 1.0


# ---------------------------------------------------------------------------
# the headline: process 2 compiles nothing
# ---------------------------------------------------------------------------
WORKER = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    # count *persistent-cache* hits/misses: a miss is an actual XLA
    # compile; backend_compile events fire even on cache hits, so
    # misses==0 is the real zero-compile assertion
    from jax._src import monitoring
    C = {"hits": 0, "misses": 0}
    def _l(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            C["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            C["misses"] += 1
    monitoring.register_event_listener(lambda e, **kw: _l(e))
    from repro.dse import SweepSpec, run_sweep, cache as dse_cache
    from repro.sims.memsys import build
    assert dse_cache.active(), "REPRO_CACHE_DIR not picked up"
    spec = SweepSpec.grid({"kind.core.think_scale": [1.0, 1.3, 1.6]})
    rows = run_sweep(build, spec, until=2000.0)
    tuned = dse_cache.stats()
    print(json.dumps({"rows": [r["virtual_time"] for r in rows],
                      **C, "artifacts": tuned}))
""")


@pytest.mark.slow
def test_second_process_performs_zero_compiles(tmp_path):
    """Two fresh processes share a campaign cache dir; the second must
    resolve *every* executable from the persistent compilation cache
    (zero cache misses == zero XLA compiles) and produce identical rows
    — plus hit the artifact store where the first populated it."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["REPRO_CACHE_DIR"] = str(tmp_path / "shared_cache")
    # a fresh jax compilation cache for this pair of processes (where
    # the variable is set, the program keeps jax's cache there): the
    # checkout's fixed cache may already hold every entry, and p1 must
    # compile
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")

    def run():
        r = subprocess.run([sys.executable, "-c", WORKER],
                           capture_output=True, text=True, timeout=900,
                           env=env)
        assert r.returncode == 0, r.stderr[-4000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first, second = run(), run()
    assert second["rows"] == first["rows"]          # caching is invisible
    assert first["misses"] > 0                      # p1 actually compiled
    assert second["misses"] == 0, second            # p2 compiled NOTHING
    # p2 resolves programs from p1's caches: the big rung executables
    # rehydrate whole (artifact `exec` hits, never reaching XLA), the
    # rest (build ops, liveness) deserialize from the persistent
    # compilation cache
    assert second["hits"] > 0
    assert first["artifacts"]["writes"] > 0
    assert second["artifacts"]["hits"] > 0


@pytest.mark.slow
def test_executables_served_by_jax_cache_are_not_persisted(tmp_path):
    """A rung executable JAX loaded from its own persistent cache is not
    re-persisted as a blob: such a blob loads, then fails at run time.
    With the blobs gone but JAX's cache warm, a process compiles nothing
    new, writes no blob, and the next process still runs the sweep."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env["REPRO_CACHE_DIR"] = str(tmp_path / "shared_cache")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")

    def run():
        r = subprocess.run([sys.executable, "-c", WORKER],
                           capture_output=True, text=True, timeout=900,
                           env=env)
        assert r.returncode == 0, r.stderr[-4000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    def blobs():
        return sorted(f for f in os.listdir(env["REPRO_CACHE_DIR"])
                      if f.startswith("exec_"))

    first = run()
    assert blobs()                                  # fresh compiles persist
    for f in blobs():
        os.unlink(os.path.join(env["REPRO_CACHE_DIR"], f))
    second = run()                                  # JAX cache serves all
    assert second["misses"] == 0 and blobs() == [], (second, blobs())
    assert run()["rows"] == second["rows"] == first["rows"]


# ---------------------------------------------------------------------------
# size-capped LRU GC
# ---------------------------------------------------------------------------
def _fake_blob(d, name, nbytes, age_s):
    import time
    p = os.path.join(d, name)
    with open(p, "wb") as fh:
        fh.write(b"x" * nbytes)
    t = time.time() - age_s
    os.utime(p, (t, t))
    return p


def test_gc_evicts_lru_down_to_cap_and_spares_store(cache_dir):
    os.makedirs(cache_dir, exist_ok=True)
    for i in range(5):                    # oldest first: ages 50..10
        _fake_blob(cache_dir, f"exec_{i:04x}.bin", 1000, age_s=50 - 10 * i)
    store_p = os.path.join(cache_dir, dse_cache.STORE_NAME)
    with open(store_p, "w") as fh:        # big store: still never evicted
        fh.write("{}" + " " * 4000)
    before = dse_cache.stats()["evictions"]
    with capture() as sink:
        n = dse_cache.gc(limit=3000)
    assert n == 2                          # two oldest blobs freed 2000B
    left = sorted(os.listdir(cache_dir))
    assert dse_cache.STORE_NAME in left
    assert "exec_0000.bin" not in left and "exec_0001.bin" not in left
    assert "exec_0004.bin" in left
    assert dse_cache.stats()["evictions"] == before + 2
    ev = [e for e in sink.events if e["kind"] == "cache.evict"]
    assert len(ev) == 2 and all(e["bytes"] == 1000 for e in ev)


def test_gc_noop_under_cap_or_unconfigured(cache_dir):
    os.makedirs(cache_dir, exist_ok=True)
    _fake_blob(cache_dir, "exec_aaaa.bin", 100, age_s=10)
    assert dse_cache.gc(limit=10_000) == 0          # under cap
    assert dse_cache.gc() == 0                      # no cap configured
    dse_cache.configure(None)
    assert dse_cache.gc(limit=1) == 0               # no cache dir


def test_configure_max_bytes_and_env_fallback(tmp_path, monkeypatch):
    d = str(tmp_path / "c")
    dse_cache.configure(d, max_bytes=123)
    try:
        assert dse_cache.max_cache_bytes() == 123
        dse_cache.configure(d)                      # reset -> env fallback
        monkeypatch.setenv(dse_cache.ENV_MAX_BYTES, "456")
        assert dse_cache.max_cache_bytes() == 456
        monkeypatch.setenv(dse_cache.ENV_MAX_BYTES, "junk")
        assert dse_cache.max_cache_bytes() is None
        monkeypatch.delenv(dse_cache.ENV_MAX_BYTES)
        assert dse_cache.max_cache_bytes() is None
    finally:
        dse_cache.configure(None)


def test_put_executable_triggers_gc(cache_dir, monkeypatch):
    """Writes keep the dir under the cap automatically: after an
    oversized put, older blobs are gone."""
    import pickle

    import jax, jax.numpy as jnp
    from jax.experimental import serialize_executable
    os.makedirs(cache_dir, exist_ok=True)
    _fake_blob(cache_dir, "exec_old0.bin", 2000, age_s=100)
    _fake_blob(cache_dir, "exec_old1.bin", 2000, age_s=50)
    sim, st = build(n_cores=2, n_reqs=6, donate=False)
    compiled = jax.jit(lambda x: x + 1).lower(jnp.zeros(4)).compile()
    # the cap holds the fresh blob plus less than one old blob, sized
    # from the blob itself (its size varies with the jax version): the
    # real AOT write blows the cap, both old blobs must age out while
    # the fresh write survives
    blob = len(pickle.dumps(serialize_executable.serialize(compiled)))
    dse_cache.configure(cache_dir, max_bytes=blob + 1000)
    dse_cache.put_executable(sim, 4, 1, compiled)
    left = sorted(os.listdir(cache_dir))
    assert "exec_old0.bin" not in left and "exec_old1.bin" not in left
    assert any(f.startswith("exec_") and f.endswith(".bin") for f in left)


def test_put_executable_counts_failed_writes(cache_dir):
    """A blob that cannot be serialized is counted and named in
    ``stats()``, not dropped in silence; the caller goes on uncached."""
    os.makedirs(cache_dir, exist_ok=True)
    sim, _ = build(n_cores=2, n_reqs=6, donate=False)
    before = dse_cache.stats()["write_failures"]
    dse_cache.put_executable(sim, 4, 1, object())     # not an executable
    s = dse_cache.stats()
    assert s["write_failures"] == before + 1
    assert s["last_write_error"]
    assert not any(f.startswith("exec_") for f in os.listdir(cache_dir))
