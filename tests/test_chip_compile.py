"""The main path compiles for a v5e chip: the TPU compiler, installed here,
compiles the program's own jitted functions for a described (not attached)
``v5e:2x2`` topology from ``ShapeDtypeStruct`` arguments.  Nothing runs;
a compile the chip's compiler refuses fails here at no chip time.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and pytest-xdist
workers must all collect the same tests.
"""
import contextlib
import os
import re

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jax.numpy.result_type(x),
                                       sharding=sharding), tree)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, mem
    return mem


def _batch(n_cores, b):
    """A memsys topology with the sweep's axes as a b-lane batch."""
    from repro.dse import BatchRunner, build_param_batch, stack_states
    from repro.sims.memsys import build
    sim, st = build(n_cores=n_cores, n_reqs=256)
    pts = [{"conn_latency[-1]": 10.0 + i, "kind.l1.extra_hit_rate": 0.0}
           for i in range(b)]
    states = jax.eval_shape(lambda s: stack_states(s, b), st)
    return BatchRunner(sim), states, build_param_batch(sim, pts)


def test_engine_loop_compiles_at_64_cores(topo):
    from repro.sims.memsys import build
    sim, st = build(n_cores=64, pattern="mixed", n_reqs=256)
    one = SingleDeviceSharding(topo.devices[0])
    args = _sds((st, np.float32(1e7), np.int32(2_000_000)), one)
    _fits(sim._run_jit.lower(*args).compile())


def test_batched_round_program_compiles_at_b256(topo):
    runner, states, params = _batch(16, 256)
    one = SingleDeviceSharding(topo.devices[0])
    lanes = np.zeros(256, np.float32), np.zeros(256, np.int32)
    fn = runner._batched_fn(256, 1)
    _fits(fn.lower(*_sds((states, params, *lanes), one)).compile())


def test_sharded_round_program_compiles_on_four_chips(topo, monkeypatch):
    from repro.core.pdes import LANE_AXIS
    from repro.dse import runner as runner_mod
    mesh = Mesh(np.array(topo.devices), (LANE_AXIS,))
    # the runner builds its mesh from jax.devices(), which sees the CPU
    # here: hand it the described chips instead
    monkeypatch.setattr(runner_mod, "lane_mesh", lambda d: mesh)
    runner, states, params = _batch(16, 256)
    lanes = np.zeros(256, np.float32), np.zeros(256, np.int32)
    fn = runner._batched_fn(256, 4)
    compiled = fn.lower(*_sds((states, params, *lanes),
                              NamedSharding(mesh, P(LANE_AXIS)))).compile()
    _fits(compiled)
    assert compiled.as_text().count("all-reduce") == 0   # lanes independent


_METADATA = re.compile(r", metadata=\{[^}]*\}")
# the source-location tables at the top of an HLO module's text
_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames")


def _without_metadata(hlo: str) -> str:
    """An HLO module's text without its op metadata and source-location
    tables, its instructions renamed in order of first use (the
    lowering numbers names after the op names that scopes extend)."""
    out, skip = [], False
    for line in hlo.split("\n"):
        if line in _LOCATION_TABLES:
            skip = True
        elif skip and line[:1] in ("%", "E"):    # computations resume
            skip = False
        if not skip:
            out.append(_METADATA.sub("", line))
    names: dict = {}
    return re.sub(r"%[\w.-]+",
                  lambda m: names.setdefault(m[0], f"%v{len(names)}"),
                  "\n".join(out))


def _compiled_with_and_without_scopes(compile_fn, monkeypatch):
    """The optimized HLO of ``compile_fn()`` as the engine's named scopes
    leave it, and with every ``jax.named_scope`` a no-op."""
    scoped = compile_fn()
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = compile_fn()
    return scoped, bare


def _scopes(hlo: str) -> set:
    return set(re.findall(r'op_name="[^"]*?(engine\.[A-Za-z0-9_.]+)', hlo))


def _assert_scopes_only_name(scoped, bare, kinds):
    assert _without_metadata(scoped) == _without_metadata(bare)
    names = _scopes(scoped)
    assert {"engine.next_event", "engine.deliver", "engine.update"} <= names
    assert {f"engine.tick.{k}" for k in kinds} <= names
    assert not _scopes(bare)


def test_named_scopes_leave_the_engine_loop_unchanged(topo, monkeypatch):
    from repro.sims.memsys import build
    one = SingleDeviceSharding(topo.devices[0])

    def compile_run():
        sim, st = build(n_cores=16, pattern="mixed", n_reqs=16)
        args = _sds((st, np.float32(1e6), np.int32(1_000_000)), one)
        return sim._run_jit.lower(*args).compile().as_text()

    _assert_scopes_only_name(
        *_compiled_with_and_without_scopes(compile_run, monkeypatch),
        ("core", "l1", "dram"))


def test_named_scopes_leave_the_round_program_unchanged(topo, monkeypatch):
    from repro.dse import BatchRunner, build_param_batch, stack_states
    from repro.sims.onira import MICROBENCHES, build_onira
    one = SingleDeviceSharding(topo.devices[0])
    progs = [np.asarray(MICROBENCHES[n]()) for n in ("ALU", "ST_LD")]
    width = max(len(p) for p in progs)
    progs = [np.pad(p, ((0, width - len(p)), (0, 0))) for p in progs]

    def compile_round():
        sim, st = build_onira(progs)
        pts = [{"conn_latency": 1.0 + i, "kind.cpu.flush_cycles": 2.0}
               for i in range(8)]
        states = jax.eval_shape(lambda s: stack_states(s, 8), st)
        lanes = np.zeros(8, np.float32), np.zeros(8, np.int32)
        fn = BatchRunner(sim)._batched_fn(8, 1)
        args = _sds((states, build_param_batch(sim, pts), *lanes), one)
        return fn.lower(*args).compile().as_text()

    _assert_scopes_only_name(
        *_compiled_with_and_without_scopes(compile_round, monkeypatch),
        ("cpu", "mem"))
