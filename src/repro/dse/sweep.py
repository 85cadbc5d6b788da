"""Sweep specification: design points over a topology's traced params.

A *design point* is a flat dict mapping axis paths to values.  Paths name
leaves of the engine's :class:`~repro.core.SimParams` pytree (traced —
hundreds of points share one compiled simulation) or, with the ``static.``
prefix, keyword arguments of the caller's build function (structural —
each distinct combination forces a rebuild/compile and forms its own
vmapped batch):

  ``conn_latency``            all connection latencies (cycles, >= 1)
  ``conn_latency[i]``         one connection (negative i counts from end)
  ``period.<kind>``           tick period of every instance of a kind
  ``period.<kind>[i]``        tick period of one instance
  ``kind.<kind>.<leaf>``      an opt-in model param (``ComponentKind.params``
                              pytree; nested dicts use dotted paths)
  ``static.<kwarg>``          build-function keyword (e.g. super_epoch)
  ``shape.<axis>``            a topology-family shape axis (instance
                              counts / wiring): lowered to traced activity
                              *masks* over one padded maximum-shape build,
                              NOT to per-shape compile groups (DSE.md
                              "Topology families")

:class:`SweepSpec` holds an ordered tuple of points, constructed by
``grid`` (cartesian product), ``random`` (uniform/log-uniform/choice
sampling), or ``explicit``.  ``split_static`` groups points by their
static-axis assignment so the runner compiles once per group; point order
within the spec is the canonical result order.

Axis paths can be checked *eagerly* — before any build or compile —
against the target simulation: pass ``validate_for=sim`` (or a
``TopologyFamily``) to a constructor, or call ``spec.validate(target)``;
unknown kinds/leaves raise a ``ValueError`` naming the bad path and the
valid axes instead of a deep ``KeyError`` mid-``run_sweep`` (which also
validates each compile group up front).
"""
from __future__ import annotations

import dataclasses
import itertools
import re
import time
import zlib
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.lax import lax as _lax

from repro.core import SimParams
from repro.obs.bus import BUS

STATIC_PREFIX = "static."
SHAPE_PREFIX = "shape."

_INDEXED = re.compile(r"^(?P<base>.*?)\[(?P<ix>-?\d+)\]$")


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """An ordered set of design points (dicts of axis path -> value)."""

    points: tuple[dict, ...]

    def __len__(self):
        return len(self.points)

    def __iter__(self):
        return iter(self.points)

    # -- constructors ------------------------------------------------------
    @staticmethod
    def grid(axes: dict[str, Sequence], validate_for=None) -> "SweepSpec":
        """Cartesian product of the axis value lists (insertion order:
        last axis varies fastest).  ``validate_for`` (a ``Simulation`` or
        ``TopologyFamily``) checks the axis paths eagerly at construction."""
        names = list(axes)
        combos = itertools.product(*(list(axes[n]) for n in names))
        spec = SweepSpec(tuple(dict(zip(names, c)) for c in combos))
        if validate_for is not None:
            spec.validate(validate_for)
        return spec

    @staticmethod
    def random(axes: dict[str, Any], n: int, seed: int = 0,
               validate_for=None) -> "SweepSpec":
        """``n`` points sampled independently per axis.  Axis specs:
        ``(lo, hi)`` uniform — float endpoints sample uniform floats,
        int endpoints sample uniform ints on the *inclusive* range —
        ``(lo, hi, 'log')`` log-uniform float, or a list/tuple of >2 (or
        non-numeric) entries = uniform choice.

        Each axis draws from its own RNG substream keyed on
        ``(seed, axis name)``: the values one axis yields under a seed
        never depend on the other axes' spec styles, their count, or
        dict order, and int axes come back as Python ints (JSON-clean
        rows; pinned by ``tests/dse/test_sweep_spec.py``).
        """
        cols = {}
        for name, spec in axes.items():
            rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
            kind, *args = parse_axis_spec(spec)
            if kind == "log":
                lo, hi = args
                cols[name] = [float(v) for v in np.exp(rng.uniform(
                    np.log(lo), np.log(hi), n))]
            elif kind == "int":
                lo, hi = args
                cols[name] = [int(v) for v in rng.integers(lo, hi + 1, n)]
            elif kind == "float":
                lo, hi = args
                cols[name] = [float(v) for v in rng.uniform(lo, hi, n)]
            else:
                values = args[0]
                cols[name] = [_py_scalar(values[int(i)])
                              for i in rng.integers(0, len(values), n)]
        out = SweepSpec(tuple(
            {name: cols[name][i] for name in axes} for i in range(n)))
        if validate_for is not None:
            out.validate(validate_for)
        return out

    @staticmethod
    def explicit(points: Iterable[dict], validate_for=None,
                 ragged: bool = False) -> "SweepSpec":
        """An ordered spec from caller-supplied point dicts.

        Points that share a ``static.*`` assignment stack into one
        vmapped compile group, so they must assign the same axis keys —
        a missing or extra key would otherwise surface much later as an
        opaque stacking/lookup failure deep in a sweep or search round.
        The mismatch raises here instead, naming the offending point
        index and keys.  Points in *different* static groups may use
        different traced axes (each group stacks separately).
        ``ragged=True`` skips the check entirely.
        """
        pts = tuple(dict(p) for p in points)
        if not ragged:
            groups: dict[frozenset, tuple[int, set]] = {}
            for i, p in enumerate(pts):
                static = frozenset(kv for kv in p.items()
                                   if kv[0].startswith(STATIC_PREFIX))
                j, keys0 = groups.setdefault(static, (i, set(p)))
                if set(p) != keys0:
                    missing = sorted(keys0 - set(p))
                    extra = sorted(set(p) - keys0)
                    raise ValueError(
                        f"explicit point {i} has inconsistent axis keys "
                        f"(missing {missing}, extra {extra} vs point "
                        f"{j}'s {sorted(keys0)}, the first point of its "
                        "static group); points that stack into one "
                        "compile group must assign identical axes "
                        "(ragged=True skips this check)")
        spec = SweepSpec(pts)
        if validate_for is not None:
            spec.validate(validate_for)
        return spec

    # -- eager validation --------------------------------------------------
    @property
    def axes(self) -> list[str]:
        """Union of axis paths across points, in first-appearance order."""
        seen: list[str] = []
        for pt in self.points:
            for k in pt:
                if k not in seen:
                    seen.append(k)
        return seen

    def has_shape_axes(self) -> bool:
        return any(k.startswith(SHAPE_PREFIX) for k in self.axes)

    def summary(self) -> dict:
        """A small JSON-safe description of the spec — axis names, value
        counts per axis, point count — for telemetry (``sweep.start``
        events carry it) and logs.  Never materializes values: a
        192-point grid summarizes to a few dozen bytes.
        """
        counts: dict[str, set] = {}
        for pt in self.points:
            for k, v in pt.items():
                counts.setdefault(k, set()).add(
                    v if isinstance(v, (int, float, str, bool)) else str(v))
        return {"n_points": len(self.points),
                "axes": {k: len(vs) for k, vs in counts.items()}}

    def validate(self, target, static_ok: Sequence[str] | None = None
                 ) -> "SweepSpec":
        """Check every axis path against ``target`` (a ``Simulation`` or a
        ``TopologyFamily``) *before* anything is built or compiled.

        Raises ``ValueError`` naming each bad path and the valid axes —
        instead of the deep ``KeyError`` an unknown kind/leaf (e.g.
        ``period.l1x``) would otherwise surface mid-``run_sweep``.
        ``static_ok`` (optional) whitelists ``static.*`` kwarg names
        (``run_sweep`` derives it from the build function's signature).
        Returns ``self`` for chaining.
        """
        family = getattr(target, "shape_max", None)
        sim = target.sim if family is not None else target
        params = sim.default_params()
        errors = []
        for path in self.axes:
            if path.startswith(STATIC_PREFIX):
                name = path[len(STATIC_PREFIX):]
                if static_ok is not None and name not in static_ok:
                    errors.append(f"{path!r}: build function accepts no "
                                  f"keyword {name!r} "
                                  f"(have {sorted(static_ok)})")
            elif path.startswith(SHAPE_PREFIX):
                name = path[len(SHAPE_PREFIX):]
                if family is None:
                    errors.append(
                        f"{path!r}: shape axes need a topology family "
                        "(a build function returning TopologyFamily); "
                        "this target is a plain Simulation")
                elif name not in family:
                    errors.append(f"{path!r}: unknown family shape axis "
                                  f"(have {sorted(family)})")
            else:
                err = axis_error(params, path)
                if err:
                    errors.append(err)
        if errors:
            raise ValueError(
                "invalid sweep axes:\n  " + "\n  ".join(errors)
                + "\nvalid axes for this target:\n  "
                + "\n  ".join(valid_axes(params, family)))
        return self

    # -- static/traced split ----------------------------------------------
    def split_static(self):
        """Group points by their ``static.*`` assignment.

        Returns ``[(static_kwargs, indices, traced_points), ...]`` in first-
        appearance order; ``indices`` map each group's points back to spec
        order.
        """
        groups: dict[tuple, tuple[dict, list, list]] = {}
        for i, pt in enumerate(self.points):
            static = {k[len(STATIC_PREFIX):]: v for k, v in pt.items()
                      if k.startswith(STATIC_PREFIX)}
            traced = {k: v for k, v in pt.items()
                      if not k.startswith(STATIC_PREFIX)}
            key = tuple(sorted(static.items()))
            if key not in groups:
                groups[key] = (static, [], [])
            groups[key][1].append(i)
            groups[key][2].append(traced)
        return list(groups.values())


# ---------------------------------------------------------------------------
def _py_scalar(v):
    """Numpy scalar -> plain Python scalar (rows stay JSON-clean)."""
    return v.item() if isinstance(v, np.generic) else v


def parse_axis_spec(spec) -> tuple:
    """Classify one :meth:`SweepSpec.random` axis spec — the single
    source of truth for spec detection, shared with the BO surrogate's
    axis encoders (``repro.dse.search.bo``) so sampling and encoding can
    never drift apart.

    Returns ``("log", lo, hi)``, ``("int", lo, hi)`` (both endpoints
    Python ints — the *inclusive* integer range), ``("float", lo, hi)``,
    or ``("choice", values)``.
    """
    spec = tuple(spec)
    is_range = (len(spec) in (2, 3)
                and all(isinstance(v, (int, float))
                        and not isinstance(v, bool)
                        for v in spec[:2])
                and (len(spec) == 2 or spec[2] == "log"))
    if not is_range:
        return ("choice", spec)
    if len(spec) == 3:
        return ("log", float(spec[0]), float(spec[1]))
    if all(isinstance(v, int) for v in spec[:2]):
        return ("int", int(spec[0]), int(spec[1]))
    return ("float", float(spec[0]), float(spec[1]))


def split_shape(point: dict) -> tuple[dict, dict]:
    """Split one design point into (shape assignment, traced assignments).

    ``shape.<axis>`` keys come back stripped of their prefix; everything
    else (the traced axes) is returned untouched for ``apply_point``.
    """
    shape = {k[len(SHAPE_PREFIX):]: v for k, v in point.items()
             if k.startswith(SHAPE_PREFIX)}
    traced = {k: v for k, v in point.items()
              if not k.startswith(SHAPE_PREFIX)}
    return shape, traced


def _leaf_paths(tree, prefix=""):
    if isinstance(tree, dict):
        out = []
        for k in sorted(tree):
            out += _leaf_paths(tree[k], f"{prefix}{k}.")
        return out
    return [prefix[:-1]] if prefix else []


def valid_axes(params: SimParams, shape_axes=None) -> list[str]:
    """Human-readable list of every sweepable axis of a target."""
    axes = ["conn_latency", "conn_latency[i]"]
    for k in sorted(params.periods):
        axes += [f"period.{k}", f"period.{k}[i]"]
    for k in sorted(params.kind):
        for leaf in _leaf_paths(params.kind[k]):
            axes.append(f"kind.{k}.{leaf}")
    for name in sorted(shape_axes or ()):
        axes.append(f"shape.{name}")
    axes.append("static.<build kwarg>")
    return axes


def _resolve(params: SimParams, path: str) -> tuple[tuple, int | None]:
    """The one parser of a traced axis path, shared by :func:`axis_error`,
    :func:`apply_point` and :func:`build_param_batch`.

    Returns the leaf the path names as a key path into ``params``
    (``("conn_latency",)``, ``("periods", kind)`` or ``("kind", kind,
    *leaf)``) and its element index (``None`` for the whole leaf).
    Unknown, ``static.*`` and ``shape.*`` paths raise ``KeyError``; an
    index out of range raises ``AssertionError``.
    """
    if path.startswith(STATIC_PREFIX):
        raise KeyError(f"static axis {path!r} reached apply_point — "
                       "route points through SweepSpec.split_static")
    if path.startswith(SHAPE_PREFIX):
        raise KeyError(f"shape axis {path!r} reached apply_point — "
                       "route points through split_shape and a "
                       "TopologyFamily (masks, not param leaves)")
    m = _INDEXED.match(path)
    base, ix = (m["base"], int(m["ix"])) if m else (path, None)
    if base == "conn_latency":
        key = ("conn_latency",)
    elif base.startswith("period."):
        kname = base[len("period."):]
        if kname not in params.periods:
            raise KeyError(f"{path!r}: unknown kind {kname!r} "
                           f"(have {sorted(params.periods)})")
        key = ("periods", kname)
    elif base.startswith("kind."):
        if ix is not None:
            raise KeyError(f"{path!r}: kind-param axes are not indexable")
        kname, _, leaf = base[len("kind."):].partition(".")
        if kname not in params.kind or not params.kind[kname]:
            raise KeyError(f"{path!r}: kind {kname!r} has no params "
                           f"(kinds with params: "
                           f"{sorted(k for k, v in params.kind.items() if v)})")
        tree = params.kind[kname]
        for k in leaf.split("."):
            tree = tree.get(k) if isinstance(tree, dict) else None
        if tree is None or isinstance(tree, dict):
            raise KeyError(f"{path!r}: no param leaf {leaf!r} on kind "
                           f"{kname!r} "
                           f"(have {_leaf_paths(params.kind[kname])})")
        return ("kind", kname, *leaf.split(".")), None
    else:
        raise KeyError(f"unknown sweep axis {path!r}")
    if ix is not None:
        n = _leaf(params, key).shape[0]
        if not -n <= ix < n:
            raise AssertionError(f"{path!r}: index {ix} out of range "
                                 f"for [{n}]")
    return key, ix


def _leaf(params: SimParams, key: tuple):
    node = getattr(params, key[0])
    for k in key[1:]:
        node = node[k]
    return node


def _with_leaf(params: SimParams, key: tuple, value) -> SimParams:
    def put(node, keys):
        if not keys:
            return value
        return {**node, keys[0]: put(node[keys[0]], keys[1:])}
    return dataclasses.replace(
        params, **{key[0]: put(getattr(params, key[0]), key[1:])})


def axis_error(params: SimParams, path: str) -> str | None:
    """``None`` if ``path`` names a traced leaf of ``params``, else a
    one-line description of why it does not."""
    try:
        _resolve(params, path)
    except (KeyError, AssertionError) as e:
        return e.args[0]
    return None


def apply_point(params: SimParams, point: dict) -> SimParams:
    """Return ``params`` with one design point's traced assignments applied.

    The single-point form of :func:`build_param_batch` (eager ``.at``
    updates on tiny arrays); unknown paths raise ``KeyError`` so typos
    fail loudly before compile.
    """
    for path, value in point.items():
        key, ix = _resolve(params, path)
        x = _leaf(params, key)
        if key[0] == "kind":
            x = jnp.asarray(value, jnp.asarray(x).dtype)
        elif ix is None:
            x = jnp.full_like(x, float(value))
        else:
            x = x.at[ix].set(jnp.asarray(value, x.dtype))
        params = _with_leaf(params, key, x)
    return params


def stack_trees(trees: Sequence) -> Any:
    """Stack a list of identically-structured pytrees into one batch
    (leading axis B), materializing fresh buffers per leaf."""
    assert trees, "empty batch"
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def stack_params(plist: Sequence[SimParams]) -> SimParams:
    """Stack per-point :class:`SimParams` into one batch (leading axis B)."""
    return stack_trees(plist)


def build_param_batch(sim, points: Sequence[dict]) -> SimParams:
    """``sim.default_params()`` + each point's assignments, stacked.

    Assembled in NumPy on the host and sent to the default device in one
    ``jax.device_put``: no device op per point.  Values, shapes, dtypes
    and ``weak_type`` equal ``stack_params([apply_point(base, p) for p in
    points])`` leaf for leaf, so the runner's executables match.  Every
    point is checked (:func:`_resolve`) before anything is transferred.
    """
    assert points, "empty batch"
    base = sim.default_params()
    host = jax.device_get(base)
    weak = jax.tree.map(lambda x: bool(getattr(x, "weak_type", False)),
                        base)
    b = len(points)
    batch = jax.tree.map(
        lambda x: np.broadcast_to(x, (b,) + x.shape).copy(), host)
    # jnp.full_like converts a whole-leaf fill through the canonical
    # float dtype (float32 with x64 off) before the leaf's own dtype
    fill_dtype = jax.dtypes.canonicalize_dtype(np.float64)
    resolved: dict[str, tuple] = {}
    retyped = set()
    for row, point in enumerate(points):
        for path, value in point.items():
            if path not in resolved:
                resolved[path] = _resolve(host, path)
            key, ix = resolved[path]
            buf = _leaf(batch, key)
            if key[0] == "kind":
                buf[row] = np.asarray(value, buf.dtype)
                retyped.add(key)
            elif ix is None:
                buf[row] = np.asarray(float(value), fill_dtype)
            else:
                buf[row, ix] = np.asarray(value, buf.dtype)
    for key in retyped:                 # jnp.asarray(value, dtype): strong
        weak = _with_leaf(weak, key, False)
    tele = BUS.active
    t0 = time.perf_counter()
    out = jax.device_put(batch)
    if tele:
        dt = time.perf_counter() - t0
        BUS.emit("transfer", what="params", lanes=b, dur=dt,
                 bytes=int(sum(x.nbytes for x in jax.tree.leaves(batch))))
        BUS.observe("dse.transfer.params_s", dt)
    # a weakly typed default leaf that no point retyped stays weak, as
    # jnp.stack keeps it; a host buffer cannot carry the flag
    return jax.tree.map(
        lambda x, w: _lax._convert_element_type(x, weak_type=True) if w
        else x, out, weak)
