"""The program's own names for a profile, and the reader of them.

- The chunk program's compiled HLO carries the step's part scopes in
  its ops' ``op_name`` metadata: the random draws and the flow-size
  sampler under ``draws``, the switch step under ``rsw`` and ``csw``,
  the Kahan fold under ``fold``, the validate guard under ``guard``.
- A profiled planned sweep records the executor's ``lcdc.*`` host spans
  in their tree, with their stats, and returns exactly what the same
  sweep returns with no profiler running.
- chipbench/progtrace.py reduces a recorded slice of a real fig2_paper
  trace (TPU v5 lite: 14 ticks around a sweep boundary, the ops' parts
  and the ``lcdc.*`` spans) to the numbers written beside it. Those were
  counted apart from progtrace: leaves found with a nesting stack, each
  op's part from its ``tf_op`` split at ``/``, ``(`` and ``)``, the
  boundary's idle intervals from a plain union of the leaves.
"""
import gzip
import json
import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simulator as S
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import devtrace, progtrace, run  # noqa: E402

SITE = FBSite(n_clusters=2, racks_per_cluster=3, servers_per_rack=4,
              csw_per_cluster=2, n_fc=2, csw_ring_links=2, fc_ring_links=4)
DATA = ROOT / "chipbench" / "data"
#: instructions that compute nothing on their own
TRIVIAL = {"constant", "parameter", "get-tuple-element", "tuple", "bitcast"}


def _runs(n=2):
    spec = TRAFFIC_SPECS["fb_hadoop"]
    return [(S.SimParams(spec=spec, site=SITE, flow_mode=1,
                         gating_enabled=bool(i % 2)), 7 + i)
            for i in range(n)]


class Hlo:
    """The instructions of an optimized HLO text: computation, opcode,
    op_name and the function of the innermost stack frame, plus the
    computations that fusions call."""

    def __init__(self, txt: str):
        def table(name):
            m = re.search(rf"^{name}\n(.*?)\n\n", txt, re.S | re.M)
            return m.group(1).split("\n") if m else []

        def ids(rows, key):
            return {int(r.split(" ", 1)[0]):
                    int(re.search(rf"{key}=(\d+)", r).group(1))
                    for r in rows}
        funcs = {int(r.split(" ", 1)[0]): r.split(" ", 1)[1].strip('"')
                 for r in table("FunctionNames")}
        files = {int(r.split(" ", 1)[0]): r.split(" ", 1)[1].strip('"')
                 for r in table("FileNames")}
        loc_fn = ids(table("FileLocations"), "function_name_id")
        loc_file = ids(table("FileLocations"), "file_name_id")
        frames = ids(table("StackFrames"), "file_location_id")
        self.fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", txt))
        self.rows = []
        comp = None
        for line in txt.split("\n"):
            head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
            if head:
                comp = head.group(1)
                continue
            m = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w\-]*)\(.*"
                         r"metadata=\{op_name=\"([^\"]*)\""
                         r"(?: stack_frame_id=(\d+))?", line)
            if m:
                loc = frames.get(int(m.group(3))) if m.group(3) else None
                self.rows.append({
                    "comp": comp, "op": m.group(1), "op_name": m.group(2),
                    "part": progtrace.part_of(m.group(2)),
                    "fn": funcs.get(loc_fn.get(loc)),
                    "file": files.get(loc_file.get(loc), "")})

    def top_level(self):
        """What the device runs as one op each: instructions outside
        fused computations (a fusion counts once, by its root's
        op_name), less the trivial ones."""
        return [r for r in self.rows
                if r["comp"] not in self.fused and r["op"] not in TRIVIAL]


@pytest.fixture(scope="module")
def chunk_hlo():
    batch = S.make_multi_site_batch(_runs())
    scen, state, fold, guard, tol = S._prepare_sweep_args(
        batch, validate=True, shard=False)
    chunk = 8
    compiled = S._sweep_runner().lower(
        batch.hull, scen, state, chunk, jnp.ones((chunk,), bool), fold,
        guard, jnp.asarray(0, jnp.int32), tol, True).compile()
    return Hlo(compiled.as_text())


def test_every_part_scope_is_in_the_chunk_program(chunk_hlo):
    parts = {r["part"] for r in chunk_hlo.rows}
    assert set(progtrace.PARTS) | {progtrace.DRAWS} <= parts


def test_draws_hold_the_sampler_and_the_random_ops(chunk_hlo):
    sampler = [r for r in chunk_hlo.rows
               if r["fn"] == "sample_flow_size_pkts"]
    assert sampler
    random = [r for r in chunk_hlo.rows if re.search(
        r"threefry2x32|random_bits|random_fold_in|random_split"
        r"|random_wrap|jit\(_uniform\)|jit\(_normal\)", r["op_name"])]
    assert random
    assert {r["part"] for r in sampler + random} == {progtrace.DRAWS}


def test_scan_body_holds_no_gather(chunk_hlo):
    # the step's table lookups (the flow-size sampler's anchors and
    # distribution row, gating's top queue) are one-hot selects: a
    # per-element gather costs the TPU far more than the select
    body = [r for r in chunk_hlo.rows if "/while/body/" in r["op_name"]]
    assert body
    assert [r["op_name"] for r in body if r["op"] == "gather"] == []


def test_switch_step_and_fold_sit_in_their_parts(chunk_hlo):
    # the ops the device runs for the switch step (inside a fused
    # computation XLA may merge an op with a like one of another part,
    # keeping one's op_name and the other's stack frame)
    switch = [r for r in chunk_hlo.top_level()
              if "/repro/kernels/" in r["file"]
              and "/while/body/" in r["op_name"]]
    assert {r["part"] for r in switch} == {"rsw", "csw"}
    # the Kahan fold runs once per chunk, after the scan
    fold = [r for r in chunk_hlo.rows if r["part"] == "fold"]
    assert fold and not any("/while/" in r["op_name"] for r in fold)
    assert {r["fn"] for r in fold} == {"_sweep_chunk_impl"}


def test_unscoped_share_of_the_scan_body_is_small(chunk_hlo):
    body = [r for r in chunk_hlo.top_level()
            if "/while/body/" in r["op_name"]]
    parts = Counter(r["part"] for r in body)
    assert body and parts[progtrace.UNSCOPED] < 0.1 * len(body), parts


@pytest.mark.parametrize("name, want", [
    ("jit(f)/while/body/vmap(edge)/draws/jit(_uniform)/add", "draws"),
    ("jit(f)/while/body/vmap(flows)/jit(_take)/gather", "flows"),
    ("jit(f)/vmap(jit(take_along_axis))/gating/gather", "gating"),
    ("jit(f)/fold/add", "fold"),
    ("jit(f)/while/body/closed_call/vmap()/lt", "unscoped"),
    ("", "unscoped"),
])
def test_part_of_unwraps_transforms(name, want):
    assert progtrace.part_of(name) == want


# ---- host spans -----------------------------------------------------------

def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_sweep_spans_nest_with_their_stats(tmp_path):
    runs = _runs()
    ticks, chunk = 40, 20
    want = S.run_sweep_planned(runs, ticks, chunk_ticks=chunk, shard=False)
    with jax.profiler.trace(str(tmp_path)):
        got = S.run_sweep_planned(runs, ticks, chunk_ticks=chunk,
                                  shard=False)
    assert json.dumps(got, sort_keys=True, default=str) == \
        json.dumps(want, sort_keys=True, default=str)

    by = {}
    for s in progtrace.record(tmp_path)["spans"]:
        by.setdefault(s[2], []).append(s)
    (sweep,) = by["lcdc.sweep"]
    assert sweep[3] == {"rows": 2, "ticks": ticks}
    for name in ("lcdc.plan", "lcdc.batch", "lcdc.init", "lcdc.dispatch",
                 "lcdc.fetch", "lcdc.finalize"):
        (s,) = by[name]
        assert _inside(s, sweep), name
    assert by["lcdc.batch"][0][3] == {"bucket": 0}
    assert by["lcdc.init"][0][3] == {"rows": 2}
    assert by["lcdc.finalize"][0][3] == {"rows": 2}
    (dispatch,) = by["lcdc.dispatch"]
    chunks = by["lcdc.chunk"]
    assert [c[3] for c in chunks] == [{"chunk": 0}, {"chunk": 1}]
    assert all(_inside(c, dispatch) for c in chunks)
    order = [by[n][0][0] for n in ("lcdc.plan", "lcdc.batch", "lcdc.init",
                                   "lcdc.dispatch", "lcdc.fetch",
                                   "lcdc.finalize")]
    assert order == sorted(order)
    assert by["lcdc.fetch"][0][0] >= dispatch[1]
    assert "lcdc.retry" not in by and "lcdc.snapshot" not in by


# ---- the reader, on a recorded slice of a chip trace ----------------------

def test_trace_dir_is_the_harness_one():
    assert progtrace.TRACE_DIR == run.TRACE_DIR


def _recorded():
    with gzip.open(DATA / "prog_slice.json.gz", "rt") as f:
        rec = json.load(f)
    want = json.loads((DATA / "prog_slice.expected.json").read_text())
    return rec, want


def _ctx(rec, want):
    class Cell:
        chips = 1
    sweeps = [{"results": [{"plan_bucket": 0}] * want["rows"]}]
    return {"cell": Cell(), "trace": devtrace.reduce(rec),
            "window": {"sweeps": sweeps}}


def test_readers_on_recorded_slice(monkeypatch):
    from chipbench.metrics import (build_programs_per_sweep,
                                   draws_us_per_tick, flows_us_per_tick,
                                   sweep_build_ms)
    rec, want = _recorded()
    monkeypatch.setattr(progtrace, "record", lambda *a: rec)
    ctx = _ctx(rec, want)
    assert devtrace.ticks(ctx["trace"]) == want["ticks"]
    for mod in (draws_us_per_tick, flows_us_per_tick, sweep_build_ms):
        name = mod.__name__.rsplit(".", 1)[-1]
        assert mod.read(ctx) == pytest.approx(want[name], rel=1e-9), name
    assert build_programs_per_sweep.read(ctx) == \
        want["build_programs_per_sweep"]


def test_parts_partition_the_recorded_chunk_program():
    rec, want = _recorded()
    parts = progtrace.part_seconds(rec, 1)
    busy = devtrace.reduce(rec)["program_busy_s"]
    assert sum(parts.values()) == pytest.approx(max(busy.values()),
                                                rel=1e-9)
    assert parts == pytest.approx(want["part_s"], rel=1e-9)


def test_readers_find_nothing_without_the_names(monkeypatch):
    """A program without the scopes and spans reads nothing, and
    nothing raises."""
    from chipbench.metrics import (build_programs_per_sweep,
                                   draws_us_per_tick, flows_us_per_tick,
                                   sweep_build_ms)
    rec, want = _recorded()
    bare = {"devices": {k: dict(d, parts=[progtrace.UNSCOPED]
                                * len(d["parts"]))
                        for k, d in rec["devices"].items()},
            "host": [], "spans": []}
    monkeypatch.setattr(progtrace, "record", lambda *a: bare)
    ctx = _ctx(bare, want)
    for mod in (draws_us_per_tick, flows_us_per_tick, sweep_build_ms,
                build_programs_per_sweep):
        assert mod.read(ctx) is None


def test_idle_by_span_covers_the_recorded_boundary():
    rec, want = _recorded()
    idle = progtrace.idle_by_span(rec)
    assert idle == pytest.approx(want["boundary_idle_s"], rel=1e-9)
    assert np.isclose(sum(idle.values()), want["boundary_idle_total_s"])
