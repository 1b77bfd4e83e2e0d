"""The trace reduction and the per-layer readers on recorded traces of
fig2_paper on a TPU v5 lite, trimmed from traced chip runs: ~4 ms (8
ticks) inside a chunk, and a sweep boundary with ~1.5 ms of ticks on
each side. The expected numbers were counted apart from the reduction:
the inside slice's leaf ops painted on a 1 ns timeline, the boundary's
leaves found with a nesting stack and their union taken by a plain
sweep."""
import json
from pathlib import Path

import pytest

from chipbench import devtrace as T
from chipbench import run

DATA = Path(__file__).resolve().parents[1] / "data"
EXPECT = json.loads((DATA / "trace_slice.expected.json").read_text())
BOUNDARY = json.loads((DATA / "boundary_slice.expected.json").read_text())


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(T.read(DATA / "trace_slice.json.gz"))


def test_busy_window_and_kernel_time(reduced):
    assert reduced["busy_s"] * 1e9 == pytest.approx(EXPECT["busy_ns"])
    assert reduced["window_s"] * 1e9 == pytest.approx(EXPECT["window_ns"])
    assert reduced["kernel_s"] * 1e9 == pytest.approx(EXPECT["kernel_ns"])
    assert sorted(reduced["kernel_calls"].values()) == EXPECT["kernel_calls"]
    assert T.ticks(reduced) == max(EXPECT["kernel_calls"])
    # the slice lies inside one chunk program: all of its busy time
    (prog,) = reduced["program_busy_s"].values()
    assert prog == pytest.approx(reduced["busy_s"])
    # and holds no sweep boundary
    assert reduced["steady"] is None


def readers(trace):
    cell = run.Cell("fig2_paper")
    peaks = json.loads((DATA.parent / "peaks.json").read_text())
    ctx = {"cell": cell, "window": {"sweeps": [{}, {}],
                                    "host_transfers": 2},
           "trace": trace, "peaks": peaks, "device_kind": "TPU v5 lite"}
    return {m["name"]: run.load_module(
        DATA.parent / "metrics" / f"{m['name']}.py", m["name"]).read(ctx)
        for m in cell.bench["per_layer"]}


def test_readers(reduced):
    read = readers(reduced)
    busy = EXPECT["busy_ns"]
    kern, ticks = EXPECT["kernel_ns"], max(EXPECT["kernel_calls"])
    # no boundary in the slice: the idle share of a period is not read
    assert read["device_idle_pct"] is None
    assert read["device_us_per_tick"] == pytest.approx(busy / 1e3 / ticks)
    assert read["switch_kernel_pct"] == pytest.approx(100 * kern / busy)
    # 189,200 B per tick (test_kernel_bytes) over 819 GB/s
    assert read["switch_roofline"] == pytest.approx(
        100 * ticks * 189200 / 819e9 / (kern / 1e9))
    assert read["host_transfers_per_sweep"] == 1.0


def test_no_device_ops_reads_nothing():
    assert T.reduce({"devices": {}, "host": []})["busy_s"] == 0.0
    assert T.ticks(T.reduce({"devices": {}, "host": []})) is None


def test_boundary_slice():
    tr = T.reduce(T.read(DATA / "boundary_slice.json.gz"))
    e = BOUNDARY
    assert tr["busy_s"] * 1e9 == pytest.approx(e["busy_ns"])
    assert tr["window_s"] * 1e9 == pytest.approx(e["window_ns"])
    assert tr["kernel_s"] * 1e9 == pytest.approx(e["kernel_ns"])
    assert sorted(tr["kernel_calls"].values()) == e["kernel_calls"]
    st = tr["steady"]
    assert st["busy_s"] * 1e9 == pytest.approx(e["steady_busy_ns"])
    assert st["window_s"] * 1e9 == pytest.approx(e["steady_window_ns"])
    assert st["ticks"] == e["steady_ticks"]
    # one period: fig2_paper's 20,000 ticks at the steady rate, plus what
    # the boundary adds beyond the ticks the slice holds
    per_w = e["steady_window_ns"] / e["steady_ticks"]
    per_b = e["steady_busy_ns"] / e["steady_ticks"]
    ticks = max(e["kernel_calls"])
    period = 20000 * per_w + e["window_ns"] - ticks * per_w
    busy = 20000 * per_b + e["busy_ns"] - ticks * per_b
    assert readers(tr)["device_idle_pct"] == pytest.approx(
        100 * (1 - busy / period))
    # the boundary's gaps are told by what the host was doing
    assert any("| host: " in label for label, _ in tr["idle_gaps"])
