"""Each fault the timed path of a cell can have, planted under a whole
run of the harness at a tiny size on the CPU (the look for a chip
skipped), turns ``correct`` false.  One chip runs every cell, so no
exchange between chips can be left out."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import CELLS, run_tiny

SWEEPS = [c for c in CELLS if c != "memsys64-mixed-single"]


def _assert_caught(name):
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["compared"]["stat_mismatches"]["value"] > 0


def test_sound_runs_pass():
    for name in CELLS:
        assert run_tiny(name)["correct"] is True


def test_single_run_returning_its_state_unchanged(monkeypatch):
    from repro.core.engine import Simulation
    monkeypatch.setattr(Simulation, "run",
                        lambda self, state, until, **kw: state)
    _assert_caught("memsys64-mixed-single")


@pytest.mark.parametrize("name", SWEEPS)
def test_campaign_returning_its_states_unchanged(monkeypatch, name):
    from repro.dse.runner import BatchRunner, stack_state_list, stack_states

    def unchanged(self, template, params_b, until, **kw):
        b = int(params_b.conn_latency.shape[0])
        return stack_state_list(template) if isinstance(
            template, (list, tuple)) else stack_states(template, b)

    monkeypatch.setattr(BatchRunner, "run_rounds", unchanged)
    _assert_caught(name)


@pytest.mark.parametrize("name", SWEEPS)
def test_half_of_each_campaign_left_out(monkeypatch, name):
    """Only the first half of a campaign's points is simulated; the rows
    of the rest are copied from it."""
    import jax
    from repro.dse.runner import BatchRunner
    run_rounds = BatchRunner.run_rounds

    def half(self, template, params_b, until, **kw):
        b = int(params_b.conn_latency.shape[0])
        h = (b + 1) // 2

        def first(x):
            return x[:h] if np.ndim(x) and len(x) == b else x

        if isinstance(template, (list, tuple)):
            template = template[:h]
        out = run_rounds(self, template, jax.tree.map(first, params_b),
                         first(np.broadcast_to(until, (b,))),
                         **{k: first(v) for k, v in kw.items()})
        return jax.tree.map(lambda x: x[np.arange(b) % h], out)

    monkeypatch.setattr(BatchRunner, "run_rounds", half)
    _assert_caught(name)


def _alter_answers(monkeypatch, system):
    """Count one statistic twice where the simulated system makes it."""
    if system == "memsys":
        from repro.sims import memsys
        tick = memsys.dram_tick

        def twice(state, ports, t):
            st, ports, res = tick(state, ports, t)
            st = dict(st, served=st["served"]
                      + res.progress.astype(jnp.int32))
            return st, ports, res

        monkeypatch.setattr(memsys, "dram_tick", twice)
    else:
        from repro.sims import onira
        tick = onira.cpu_tick

        def twice(state, ports, t, params):
            st, ports, res = tick(state, ports, t, params)
            st = dict(st, retired=st["retired"]
                      + (st["pc"] != state["pc"]).astype(jnp.int32))
            return st, ports, res

        monkeypatch.setattr(onira, "cpu_tick", twice)


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_it_is_produced(monkeypatch, name):
    _alter_answers(monkeypatch, "onira" if "onira" in name else "memsys")
    _assert_caught(name)
