"""Persistent cross-process caching for DSE campaigns (DSE.md "Sharded
sweeps and the persistent cache").

A fleet of short-lived sweep/search jobs (CI shards, search workers,
one-config-per-process campaigns) pays the family cold compile — ~7s on
the memsys family, 0.53 shapes/s cold vs 51.5 warm (BENCH_struct.json)
— once *per process* unless compiled executables outlive the process.
This module makes them outlive it, at two layers:

* **XLA executables** — :func:`enable_jax_cache` turns on
  ``jax.experimental.compilation_cache`` with the min-compile-time/
  min-entry-size thresholds dropped to zero so every sweep executable
  persists.  The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says
  (JAX reads the variable itself, and nothing here sets another
  directory); without it, at :data:`JAX_CACHE_DIR`, one fixed path
  inside the checkout — the path is part of what a later process must
  find again, so it never carries a temporary name, a pid or a time.
  ``run_sweep`` calls :func:`ensure_enabled` on entry
  ("enable-on-first-sweep"), which turns it on whenever a campaign dir
  is configured, so the second process of a campaign deserializes
  instead of compiling.
* **Whole AOT executables** — the jax persistent cache skips XLA
  *compilation* but a fresh process still re-traces and re-lowers every
  program, and on the batched while-loop engine trace+lower is seconds
  per rung — the dominant warm-start cost once compiles are cached.
  :func:`get_executable` / :func:`put_executable` persist the runner's
  big single-device batched executables whole (``jax.experimental
  .serialize_executable``; one blob file per ``(sim signature, batch
  size, shard topology, platform)``), so the second process *loads*
  each rung executable in ~0.1s with **no tracing at all**.  Sharded
  rungs are not persisted whole (``BatchRunner._batched_fn`` says why);
  they still skip their compiles through the jax cache.  A loaded
  executable is the same compiled binary — results are bit-identical by
  construction, donation semantics included.
* **Repro's own artifacts** — the executables are necessary but not
  sufficient: a fresh process must also *ask for the same executables*.
  :class:`DseCache` is a small JSON store (one file in the same cache
  dir) keyed on ``(simulation structural signature, batch size, shard
  topology, jax + repro cache version)`` that persists the three
  decisions a warm process made so a cold one can repeat them exactly:

  - the **autotuned chunk-ladder winner** (``tuned_top``) — otherwise
    the second process re-probes and may pick a different rung, missing
    the persisted executables entirely;
  - the **warm-ladder rung set** (``rungs``) — which batch sizes a
    sweep of this shape actually compiled, so ``run_rounds`` can
    pre-warm them all from the persistent cache before the first timed
    round instead of faulting them in mid-sweep;
  - the **family max-shape union** (``family``) — ``memoize_build``
    grows a family's padded maximum across search rounds; persisting
    the union lets the next process build the family at the final
    maximum in one shot (one build, and an executable key that matches
    the cached one).

Every lookup emits ``cache.hit`` / ``cache.miss`` (and writes emit
``cache.write``) on the telemetry bus with payload byte sizes, plus a
``dse.cache.hit_rate`` gauge the ``/campaign`` dashboard surfaces —
a campaign that silently misses its cache is a perf bug worth seeing.

The campaign directory (``REPRO_CACHE_DIR`` or :func:`configure`) holds
the artifact store and the executable blobs.  It is size-capped:
:func:`gc` evicts least-recently-used files (executable hits bump
mtime) down to ``REPRO_CACHE_MAX_BYTES`` / ``configure(max_bytes=...)``,
emitting ``cache.evict`` per file — so a long-lived shared cache dir
serves many campaigns without growing forever.  The artifact store itself is never evicted (a few KB of
decisions whose loss would cost a re-probe).

Nothing here is load-bearing for correctness: with no cache dir
configured every function is a cheap no-op, artifacts only shortcut
decisions that would otherwise be re-derived, and a corrupt or
concurrently-rewritten store file degrades to a miss.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import tempfile
import threading
import weakref

import jax
from jax._src import monitoring

from repro.obs.bus import BUS

ENV_DIR = "REPRO_CACHE_DIR"
ENV_MAX_BYTES = "REPRO_CACHE_MAX_BYTES"
ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed directory at the root of the checkout (.gitignore'd)
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# Bump when the artifact semantics change (keys embed it, so old stores
# simply stop matching instead of poisoning new processes).
CACHE_VERSION = 2

STORE_NAME = "repro_dse_artifacts.json"

_lock = threading.Lock()
_cfg: dict = {"dir": None, "jax_enabled": False, "max_bytes": None}
_store: "DseCache | None" = None
_counts = {"hits": 0, "misses": 0, "writes": 0, "evictions": 0,
           "write_failures": 0}
_last_write_error: str | None = None    # repr of the newest failed write
_jax_hits = 0       # compiles this process served from JAX's own cache


def _count_jax_hit(event: str, **_) -> None:
    global _jax_hits
    if event == "/jax/compilation_cache/cache_hits":
        _jax_hits += 1


monitoring.register_event_listener(_count_jax_hit)

_SIM_SIGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------
def configure(cache_dir: str | None,
              max_bytes: int | None = None) -> None:
    """Set (or clear, with ``None``) the campaign cache directory.

    Precedence: an explicit ``configure()`` beats the ``REPRO_CACHE_DIR``
    environment variable.  The jax compilation cache is wired lazily by
    :func:`ensure_enabled` (``run_sweep`` calls it on entry), so merely
    configuring a directory costs nothing.

    ``max_bytes`` caps the cache directory's total size: when a write
    pushes it over, :func:`gc` evicts least-recently-used files until it
    fits (``None`` falls back to the ``REPRO_CACHE_MAX_BYTES``
    environment variable; with neither set the cache grows unbounded).
    Each ``configure()`` call resets the cap, so a test that sets one
    cannot leak it into the next.
    """
    global _store
    with _lock:
        _cfg["dir"] = cache_dir
        _cfg["max_bytes"] = None if max_bytes is None else int(max_bytes)
        _store = None


def cache_dir() -> str | None:
    """The effective cache directory, or ``None`` when caching is off."""
    return _cfg["dir"] or os.environ.get(ENV_DIR) or None


def max_cache_bytes() -> int | None:
    """The effective size cap for :func:`gc`, or ``None`` (unbounded).
    ``configure(max_bytes=...)`` beats ``REPRO_CACHE_MAX_BYTES``."""
    if _cfg["max_bytes"] is not None:
        return int(_cfg["max_bytes"])
    env = os.environ.get(ENV_MAX_BYTES)
    try:
        return int(env) if env else None
    except ValueError:
        return None


def active() -> bool:
    """Whether a cache directory is configured (artifact lookups and the
    persistent compilation cache are live)."""
    return cache_dir() is not None


def enable_jax_cache() -> str:
    """Idempotently turn on JAX's persistent compilation cache; returns
    its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and this sets no other directory; otherwise the cache
    goes to :data:`JAX_CACHE_DIR`.  Drops jax's min-compile-time and
    min-entry-size thresholds so every executable persists (the default
    1s floor would skip the small liveness/rung programs whose
    re-compiles still stall a fresh process).
    """
    d = os.environ.get(ENV_JAX_DIR) or JAX_CACHE_DIR
    with _lock:
        if _cfg["jax_enabled"]:
            return d
        if not os.environ.get(ENV_JAX_DIR):
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax latches the enabled/disabled decision at the *first*
        # compile of the process: a build that jitted anything before
        # this point initialized the cache as "no directory", and the
        # config update alone never re-checks.  Un-latch so the next
        # compile re-initializes against the directory set above.
        from jax._src import compilation_cache as _cc
        if _cc._cache_initialized and _cc._cache is None:
            _cc.reset_cache()
        _cfg["jax_enabled"] = True
    if BUS.active:
        BUS.emit("cache.enable", dir=d, jax=jax.__version__)
    return d


def ensure_enabled() -> bool:
    """Turn on JAX's persistent compilation cache (:func:`enable_jax_cache`)
    when a campaign cache directory is configured; returns whether
    campaign caching is active.  Called by ``run_sweep`` on entry — the
    first sweep of a process enables the cache for everything after it.
    """
    if cache_dir() is None:
        return False
    enable_jax_cache()
    gc()     # shrink a pre-existing over-cap dir at startup, not mid-sweep
    return True


def store() -> "DseCache | None":
    """The process-wide artifact store (``None`` when caching is off)."""
    global _store
    d = cache_dir()
    if d is None:
        return None
    with _lock:
        if _store is None or _store.path != os.path.join(d, STORE_NAME):
            _store = DseCache(os.path.join(d, STORE_NAME))
    return _store


def jax_cache_hits() -> int:
    """Compiles this process has served from JAX's persistent compilation
    cache so far.  An executable JAX loaded from that cache re-serializes
    into a blob that fails at run time (XLA:CPU "Function ... not found"),
    so ``BatchRunner`` persists only executables it actually compiled."""
    return _jax_hits


def stats() -> dict:
    """Process-wide artifact hit/miss/write counts (tests + dashboards),
    plus ``write_failures`` — executable blobs that could not be
    serialized or written — and ``last_write_error``, the newest such
    failure's ``repr`` (``None`` if there was none)."""
    return dict(_counts, last_write_error=_last_write_error)


def _note(kind: str, key: str, hit: bool, nbytes: int = 0) -> None:
    _counts["hits" if hit else "misses"] += 1
    if BUS.active:
        BUS.emit("cache.hit" if hit else "cache.miss", what=kind, key=key,
                 bytes=nbytes)
        BUS.count("dse.cache.hits" if hit else "dse.cache.misses")
        seen = _counts["hits"] + _counts["misses"]
        BUS.gauge("dse.cache.hit_rate", _counts["hits"] / seen)


# ---------------------------------------------------------------------------
# size-capped LRU GC
# ---------------------------------------------------------------------------
def gc(limit: int | None = None) -> int:
    """Evict least-recently-used cache files until the directory fits
    the size cap; returns the number of files evicted.

    Candidates are every file under the campaign cache dir — AOT
    executable blobs (``exec_*.bin``) and anything else kept there —
    *except* the artifact store (:data:`STORE_NAME`, a few KB of
    decisions that regenerating would cost a re-probe) and in-progress
    temp files.  Recency is file mtime: :func:`get_executable` bumps it
    on every hit, so a campaign's hot rung executables survive while a
    long-dead topology's blobs age out.  ``limit`` overrides the
    configured cap (:func:`max_cache_bytes`); with no cap (or no cache
    dir) this is a no-op.  Every eviction emits a ``cache.evict`` event
    and bumps ``dse.cache.evictions``; the post-GC directory size lands
    on the ``dse.cache.bytes`` gauge.

    Called automatically after every executable write (the only writes
    big enough to matter) and once at :func:`ensure_enabled` — a
    pre-existing over-cap directory shrinks at startup, not mid-sweep.
    """
    d = cache_dir()
    cap = max_cache_bytes() if limit is None else int(limit)
    if d is None or cap is None:
        return 0
    entries: list[tuple[int, int, str]] = []
    total = 0
    for root, _, files in os.walk(d):
        for name in files:
            if name == STORE_NAME or name.startswith(".dse_"):
                continue
            p = os.path.join(root, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime_ns, st.st_size, p))
            total += st.st_size
    if BUS.active:
        BUS.gauge("dse.cache.bytes", total)
    if total <= cap:
        return 0
    evicted = 0
    freed = 0
    for _, size, p in sorted(entries):        # oldest mtime first
        if total - freed <= cap:
            break
        try:
            os.unlink(p)
        except OSError:                       # raced another process
            continue
        freed += size
        evicted += 1
        _counts["evictions"] += 1
        if BUS.active:
            BUS.emit("cache.evict", path=os.path.relpath(p, d),
                     bytes=size)
            BUS.count("dse.cache.evictions")
    if BUS.active and evicted:
        BUS.gauge("dse.cache.bytes", total - freed)
    return evicted


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------
def _hash(obj) -> str:
    blob = json.dumps(obj, sort_keys=True, default=repr).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def sim_signature(sim) -> str:
    """A structural signature of a built :class:`~repro.core.Simulation`,
    stable across processes: kind layout + connection count + the
    abstract (shape, dtype) tree of its default params.

    Two processes that build the same topology get the same signature;
    any structural difference (instance counts, port counts, padding,
    super-epoch, param schema) changes it — exactly the things that
    change the compiled executables an artifact points at.
    """
    sig = _SIM_SIGS.get(sim)
    if sig is None:
        params = sim.default_params()
        leaves, treedef = jax.tree.flatten(params)
        sig = _SIM_SIGS[sim] = _hash({
            "kinds": [(k.name, int(k.n_instances), int(k.n_ports))
                      for k in sim.kinds],
            "n_conn": int(sim.n_conn),
            "cap_phys": int(sim.cap_phys),
            "super_epoch": int(sim.super_epoch),
            "donate": bool(sim.donate),
            "params": [(str(jax.numpy.shape(x)),
                        str(jax.numpy.asarray(x).dtype)) for x in leaves],
            "treedef": str(treedef),
        })
    return sig


def _key(kind: str, **parts) -> str:
    return f"{kind}:" + _hash(dict(parts, jax=jax.__version__,
                                   cache_version=CACHE_VERSION))


def family_build_key(build_fn, args: tuple, kwargs: dict) -> str:
    """Key for a memoized family build: the build function's identity
    plus its non-shape arguments (values via ``repr`` — build kwargs are
    plain scalars/strings in practice)."""
    fn = getattr(build_fn, "__wrapped__", build_fn)
    return _key("family",
                fn=f"{getattr(fn, '__module__', '?')}."
                   f"{getattr(fn, '__qualname__', repr(fn))}",
                args=[repr(a) for a in args],
                kwargs={k: repr(v) for k, v in sorted(kwargs.items())})


# ---------------------------------------------------------------------------
# the JSON artifact store
# ---------------------------------------------------------------------------
class DseCache:
    """A tiny persistent key→JSON-value store (one file, atomic writes).

    Reads reload the file only when its mtime/size changed (cheap stat
    per lookup); writes read-merge-replace under a process lock with
    ``os.replace`` so concurrent processes never see a torn file.  Two
    processes racing on the *same* key last-write-wins — every value
    here is a shortcut, not a source of truth, so that is safe.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._data: dict = {}
        self._stamp: tuple | None = None

    # -- file I/O ----------------------------------------------------------
    def _refresh(self) -> None:
        try:
            st = os.stat(self.path)
            stamp = (st.st_mtime_ns, st.st_size)
        except OSError:
            self._data, self._stamp = {}, None
            return
        if stamp == self._stamp:
            return
        try:
            with open(self.path) as fh:
                raw = json.load(fh)
            self._data = raw.get("entries", {}) \
                if raw.get("version") == CACHE_VERSION else {}
        except (OSError, ValueError):     # torn/corrupt file -> miss
            self._data = {}
        self._stamp = stamp

    def _flush(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        body = {"version": CACHE_VERSION, "entries": self._data}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(self.path) or ".",
                                   prefix=".dse_cache_")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump(body, fh, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError:                    # read-only dir: stay in-memory
            try:
                os.unlink(tmp)
            except OSError:
                pass
        try:
            st = os.stat(self.path)
            self._stamp = (st.st_mtime_ns, st.st_size)
        except OSError:
            self._stamp = None

    # -- API ---------------------------------------------------------------
    def get(self, key: str, kind: str = "artifact"):
        with self._lock:
            self._refresh()
            v = self._data.get(key)
        hit = v is not None
        _note(kind, key, hit,
              len(json.dumps(v).encode()) if hit else 0)
        return v

    def put(self, key: str, value, kind: str = "artifact") -> None:
        blob = json.loads(json.dumps(value))   # force JSON-cleanliness now
        with self._lock:
            self._refresh()                    # merge concurrent writers
            self._data[key] = blob
            self._flush()
        _counts["writes"] += 1
        if BUS.active:
            BUS.emit("cache.write", what=kind, key=key,
                     bytes=len(json.dumps(blob).encode()))
            BUS.count("dse.cache.writes")


# ---------------------------------------------------------------------------
# artifact accessors (all no-ops without a configured cache dir)
# ---------------------------------------------------------------------------
def _maybe_enable_at_import() -> None:
    """With ``REPRO_CACHE_DIR`` in the environment, wire the jax cache
    the moment ``repro.dse`` is imported — jax latches the cache
    decision at the process's *first* compile, and builds typically
    compile before the first sweep; enabling early means those
    executables persist too, so the second process of a campaign starts
    with a complete cache instead of back-filling build-time programs."""
    if os.environ.get(ENV_DIR):
        ensure_enabled()


_maybe_enable_at_import()


def get_tuned_top(sim, devices: int) -> int | None:
    """The persisted autotune winner for (this topology, this shard
    topology), or ``None``."""
    s = store()
    if s is None:
        return None
    v = s.get(_key("tuned_top", sim=sim_signature(sim), devices=devices),
              kind="tuned_top")
    return int(v) if v is not None else None


def put_tuned_top(sim, devices: int, top: int) -> None:
    s = store()
    if s is not None:
        s.put(_key("tuned_top", sim=sim_signature(sim), devices=devices),
              int(top), kind="tuned_top")


def get_rung_set(sim, b: int, devices: int) -> list[int] | None:
    """The rung batch sizes a previous process compiled for a B-point
    sweep of this topology at this shard topology."""
    s = store()
    if s is None:
        return None
    v = s.get(_key("rungs", sim=sim_signature(sim), b=b, devices=devices),
              kind="rungs")
    return sorted(int(r) for r in v) if v else None


def put_rung_set(sim, b: int, devices: int, rungs) -> None:
    s = store()
    if s is None:
        return
    key = _key("rungs", sim=sim_signature(sim), b=b, devices=devices)
    with s._lock:
        s._refresh()
        old = s._data.get(key) or []
    merged = sorted({int(r) for r in (*old, *rungs)})
    if merged != sorted(int(r) for r in old):
        s.put(key, merged, kind="rungs")


def get_family_shape(build_key: str) -> dict | None:
    """The persisted max-shape union of a memoized family build."""
    s = store()
    if s is None:
        return None
    v = s.get(build_key, kind="family")
    return {k: int(x) for k, x in v.items()} if v else None


def put_family_shape(build_key: str, shape_max: dict) -> None:
    s = store()
    if s is None:
        return
    with s._lock:
        s._refresh()
        old = s._data.get(build_key) or {}
    merged = dict(old)
    for k, v in shape_max.items():
        merged[k] = max(int(v), int(merged.get(k, 0)))
    if merged != old:
        s.put(build_key, merged, kind="family")


# ---------------------------------------------------------------------------
# whole-executable persistence (skips trace + lower, not just compile)
# ---------------------------------------------------------------------------
def _platform() -> str:
    """Platform the next computation runs on: the ``jax.default_device``
    in force (e.g. a CPU reference run beside a chip), else the default
    backend.  A blob compiled for one platform must never serve another."""
    d = jax.config.jax_default_device
    if d is None:
        return jax.default_backend()
    return d if isinstance(d, str) else d.platform


def _exec_key(sim, b: int, devices: int) -> str:
    return _key("exec", sim=sim_signature(sim), b=int(b),
                devices=int(devices), platform=_platform())


def _exec_path(key: str) -> str:
    return os.path.join(cache_dir(), f"exec_{key.split(':', 1)[1]}.bin")


def get_executable(sim, b: int, devices: int):
    """Rehydrate the persisted AOT executable for (topology, batch size,
    shard topology), or ``None``.

    A load failure of any sort — missing blob, torn write, different
    backend, an executable serialized under an incompatible device
    topology, an older jax — degrades to a miss and the caller compiles
    normally (then re-persists, healing the store).
    """
    if not active():
        return None
    key = _exec_key(sim, b, devices)
    try:
        with open(_exec_path(key), "rb") as fh:
            payload = fh.read()
        from jax.experimental import serialize_executable as _se
        blob, in_tree, out_tree = pickle.loads(payload)
        fn = _se.deserialize_and_load(blob, in_tree, out_tree)
    except Exception:
        _note("exec", key, False)
        return None
    try:
        os.utime(_exec_path(key))     # LRU recency: a hit is a touch
    except OSError:
        pass
    _note("exec", key, True, len(payload))
    return fn


def _write_failed(err: BaseException) -> None:
    """Count a blob that could not be persisted (the sweep goes on with
    the in-memory executable; ``stats()`` reports the failure)."""
    global _last_write_error
    _counts["write_failures"] += 1
    _last_write_error = repr(err)
    if BUS.active:
        BUS.count("dse.cache.write_failures")


def put_executable(sim, b: int, devices: int, compiled) -> None:
    """Serialize an AOT-compiled batched executable into the cache dir
    (atomic write).  A failure to serialize or write is counted in
    :func:`stats` (``write_failures``) and the run goes on uncached."""
    if not active():
        return
    key = _exec_key(sim, b, devices)
    try:
        from jax.experimental import serialize_executable as _se
        payload = pickle.dumps(_se.serialize(compiled))
    except Exception as e:
        _write_failed(e)
        return
    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".dse_exec_")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(payload)
        os.replace(tmp, _exec_path(key))
    except OSError as e:               # read-only dir: skip persistence
        try:
            os.unlink(tmp)
        except OSError:
            pass
        _write_failed(e)
        return
    _counts["writes"] += 1
    if BUS.active:
        BUS.emit("cache.write", what="exec", key=key, bytes=len(payload))
        BUS.count("dse.cache.writes")
    gc()          # keep the dir under the size cap as it grows
