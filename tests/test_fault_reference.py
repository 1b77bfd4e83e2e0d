"""The fig2_faults cell at a CPU size: the plain fault reference
(chipbench/reference/fig2_faults.py) against the program, through the
benchmark harness.

- The harness reads ``correct`` true for the program, and false for the
  reference in bfloat16 and for the program with a defect planted in its
  fault path: the fault-drop move skipped; the fallback forced off; dead
  ports still served by the switch kernel; dead transceivers still
  drawing power; the repair countdown stopped.
- With every optics knob at zero the fault reference is fig2.py's,
  exactly.
- Each fault family fires over the LC/DC rows, and no row loses
  connectivity while the fallback is on.

The cell is chipbench/tests/cells.py's small site (2 clusters of 8
racks, 2 planes, 2 FCs; 600 ticks in two chunks) with the configuration's
own 48 servers a rack: with cells.py's 8 the uplink queues are empty
whenever a link dies, so no packet ever reaches the fault-drop bin and a
defect there would change nothing.
"""
import contextlib
import copy
import inspect
import io
import json
import sys
import textwrap
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, run  # noqa: E402
from chipbench.tests.cells import small_cell  # noqa: E402

SEED = 2 ** 31 + 7
FAMILIES = ("link_fault_frac", "wake_retries", "forced_wakes")


def fault_cell():
    cell = small_cell("fig2_faults")
    cell.cfg["site"]["servers_per_rack"] = run.Cell(
        "fig2_faults").cfg["site"]["servers_per_rack"]
    return cell


@pytest.fixture(scope="module")
def cell():
    return fault_cell()


@pytest.fixture
def fresh_programs():
    """The chunk program is traced anew, so a defect planted in the step
    reaches it, and nothing planted outlives the test."""
    from repro.core import simulator as S
    S._sweep_runner.cache_clear()
    jax.clear_caches()
    yield
    S._sweep_runner.cache_clear()
    jax.clear_caches()


def drive(cell) -> dict:
    args = types.SimpleNamespace(workload=cell.name, seed=SEED,
                                 seconds=0.1, trace=0)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.run(args, cell=cell, look_for_chip=False) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_program_is_correct(cell):
    res = drive(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["edge_gap"]["value"] == 0.0


def test_control_is_not_correct(cell):
    """The reference in bfloat16 in the program's place fails."""
    seeds = run.row_seeds(11, 1, len(cell.rows))
    ref = cell.reference()
    want = ref.reference_metrics(cell.cfg, cell.rows, seeds, cell.n_ticks)
    got = ref.reference_metrics(cell.cfg, cell.rows, seeds, cell.n_ticks,
                                dtype=jnp.bfloat16)
    ck = cell.traffic["check"]
    ok, table = check.verdict(ck, check.numbers(ck, got, want))
    assert not ok, table


def skip_fault_drop(monkeypatch):
    """Links die, but the queue of a dying link is neither moved to the
    fault-drop bin nor emptied."""
    from repro.core import gating
    real = gating.fault_arrivals

    def arrivals(*a, **kw):
        timer, new = real(*a, **kw)
        return timer, jnp.zeros_like(new)
    monkeypatch.setattr(gating, "fault_arrivals", arrivals)


def no_fallback(monkeypatch):
    """The min-connectivity fallback is forced off."""
    from repro.core import gating
    real = gating.gate_step

    def gate_step(*a, **kw):
        if "fallback" in kw:
            kw["fallback"] = False
        return real(*a, **kw)
    monkeypatch.setattr(gating, "gate_step", gate_step)


def no_repair(monkeypatch):
    """A dead link never counts its repair down: it stays dead."""
    from repro.core import gating
    real = gating.fault_arrivals

    def arrivals(timer, *a, **kw):
        new_timer, new = real(timer, *a, **kw)
        return jnp.where(timer > 0, timer, new_timer), new
    monkeypatch.setattr(gating, "fault_arrivals", arrivals)


def mutate_step(monkeypatch, edits):
    """``make_sim_step`` with each ``(old, new)`` of ``edits`` applied
    to its source, each ``old`` found exactly once."""
    from repro.core import simulator as S
    src = textwrap.dedent(inspect.getsource(S.make_sim_step))
    for old, new in edits:
        assert src.count(old) == 1, old
        src = src.replace(old, new)
    scope = dict(vars(S))
    exec(compile(src, S.__file__, "exec"), scope)
    monkeypatch.setattr(S, "make_sim_step", scope["make_sim_step"])


def dead_ports_served(monkeypatch):
    """The switch kernel's per-link mask leaves out hard faults: a dead
    port still accepts and serves, on both tiers."""
    mutate_step(monkeypatch, [
        ("valid=rack_valid[:, None] & rsw_ok", "valid=rack_valid[:, None]"),
        ("valid=csw_valid[:, None] & csw_ok", "valid=csw_valid[:, None]")])


def dead_transceivers_lit(monkeypatch):
    """A hard-faulted transceiver still draws power while the
    controller holds it powered."""
    mutate_step(monkeypatch, [
        ("rack_valid[:, None] & (rsw_timer == 0),", "rack_valid[:, None],"),
        ("csw_valid[:, None] & (csw_timer == 0),", "csw_valid[:, None],")])


DEFECTS = (skip_fault_drop, no_fallback, dead_ports_served,
           dead_transceivers_lit, no_repair)


@pytest.mark.parametrize("defect", DEFECTS)
def test_fault_path_defect_is_not_correct(monkeypatch, fresh_programs,
                                          defect):
    cell = fault_cell()
    defect(monkeypatch)
    res = drive(cell)
    assert not res["correct"], res["checks"]


def test_zero_optics_is_the_fig2_reference(cell):
    cfg = copy.deepcopy(cell.cfg)
    cfg["optics"].update(wake_fail_prob=0.0, wake_jitter_frac=0.0,
                         link_mtbf_ticks=0.0, repair_ticks=0,
                         plane_fail_prob=0.0)
    seeds = run.row_seeds(SEED, 1, len(cell.rows))
    base = run.load_module(run.BENCH / "reference" / "fig2.py",
                           "chipbench_reference_fig2")
    want = base.reference_metrics(cfg, cell.rows, seeds, cell.n_ticks)
    got = cell.reference().reference_metrics(cfg, cell.rows, seeds,
                                             cell.n_ticks)
    for g, w in zip(got, want):
        assert {k: g[k] for k in w} == w
        assert all(g[k] == 0.0 for k in FAMILIES + (
            "fault_dropped_pkts", "conn_loss_ticks", "delay_fault_stall_us"))


def test_every_fault_family_fires(cell):
    eng = run.Engine(cell)
    res = eng.sweep(eng.runs(SEED, 1), cell.n_ticks)
    lcdc = [r for r, row in zip(res, cell.rows) if row["gating"]]
    assert len(lcdc) == 5
    for m in FAMILIES:
        assert sum(r[m] for r in lcdc) > 0, m
    assert [r["conn_loss_ticks"] for r in res] == [0.0] * len(res)
