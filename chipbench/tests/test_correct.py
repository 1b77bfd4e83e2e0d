"""``correct`` at a CPU size: true for the program as it is, false for
the control (the reference in bfloat16) and for each fault a cell can
have, planted in the timed path underneath a whole run of the harness
(the look for a chip skipped)."""
import contextlib
import io
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run
from chipbench.tests.cells import small_cell

CELLS = ("fig2_paper", "fig2_flows", "fig2_util_wide")


@pytest.fixture(autouse=True)
def fresh_programs():
    """Every test traces the chunk program anew, so a fault planted in
    the step reaches it."""
    from repro.core import simulator as S
    S._sweep_runner.cache_clear()
    jax.clear_caches()
    yield
    S._sweep_runner.cache_clear()
    jax.clear_caches()


def drive(cell) -> dict:
    args = types.SimpleNamespace(workload=cell.name, seed=2 ** 31 + 7,
                                 seconds=0.1, trace=0)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.run(args, cell=cell, look_for_chip=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = drive(small_cell(name))
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference in bfloat16 in the program's place fails."""
    cell = small_cell(name)
    rows = cell.rows[:int(cell.traffic["check"]["sample_rows"])]
    seeds = run.row_seeds(11, 1, len(rows))
    ref = cell.reference()
    want = ref.reference_metrics(cell.cfg, rows, seeds, cell.n_ticks)
    got = ref.reference_metrics(cell.cfg, rows, seeds, cell.n_ticks,
                                dtype=jnp.bfloat16)
    ok, table = check.verdict(cell.traffic["check"],
                              check.numbers(cell.traffic["check"], got, want))
    assert not ok, table


def unchanged_state(monkeypatch, S, cell):
    """The step returns its state unchanged."""
    monkeypatch.setattr(S, "make_sim_step",
                        lambda hull: (lambda scen, state: state))


def half_batch(monkeypatch, S, cell):
    """Half of the batch is left out; its rows get the mean of the rest."""
    real = S.run_sweep_planned

    def planned(runs, n_ticks, **kw):
        h = len(runs) // 2
        done = real(runs[:h], n_ticks, **kw)
        mean = {k: float(np.mean([r[k] for r in done])) for k, v in
                done[0].items() if isinstance(v, (int, float))
                and not isinstance(v, bool)}
        return done + [dict(done[0], **mean) for _ in runs[h:]]
    monkeypatch.setattr(S, "run_sweep_planned", planned)


def altered_answer(monkeypatch, S, cell):
    """An answer is altered where it is produced: the first edge metric
    of every scenario is 1% high."""
    real = S._finalize
    metric = cell.traffic["check"]["numbers"]["edge_gap"]["metrics"][0]

    def finalize(*a, **kw):
        out = real(*a, **kw)
        out[metric] *= 1.01
        return out
    monkeypatch.setattr(S, "_finalize", finalize)


def lossy_kernel(monkeypatch, S, cell):
    """The switch kernel loses a tenth of what it serves: those packets
    leave their queue and reach no one."""
    from repro.kernels import ops
    real = ops.switch_step

    def step(*a, **kw):
        out = list(real(*a, **kw))
        out[1] = out[1] * 0.9
        return tuple(out)
    monkeypatch.setattr(ops, "switch_step", step)


@pytest.mark.parametrize("fault", (unchanged_state, half_batch,
                                   altered_answer, lossy_kernel))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(monkeypatch, name, fault):
    from repro.core import simulator as S
    cell = small_cell(name)
    fault(monkeypatch, S, cell)
    res = drive(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_lost_packets_break_conservation(monkeypatch, name):
    """The packet identity alone catches the lossy kernel."""
    from repro.core import simulator as S
    cell = small_cell(name)
    lossy_kernel(monkeypatch, S, cell)
    t = drive(cell)["checks"]["pkt_cons_gap"]
    assert t["value"] > t["limit"], t
