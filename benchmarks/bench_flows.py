"""Flow-level workload benchmark: the FCT-slowdown frontier of LC/DC
gating under heavy-tailed DCN workloads, plus the CI correctness gate
for the flow engine.

One batched sweep (a single compile: every flow knob is a ``Scenario``
array leaf) runs a grid of workloads x operating modes — the websearch
and datamining flow-size distributions at light and loaded arrival
rates, LC/DC gating vs the always-on baseline, an incast row that
saturates a shrunken flow table, and a pair of ``flow_mode=0`` rows —
and reports, per row, the energy savings the gating still achieves
against what it costs in flow completion time: per-size-class FCT
p50/p99 and slowdown vs the ideal-bandwidth baseline.

The run doubles as the flow-model regression gate (``--check-baseline``
against the ``bench_flows`` section of benchmarks/baselines.json, the
CI flow-canary job):

  * ``flow_mode=0`` rows report every flow metric as EXACTLY zero (the
    flow engine must be inert when disabled — the bit-parity contract),
  * flow conservation is EXACT in every row, eviction included
    (started == completed + evicted + still-in-table),
  * every slowdown percentile is >= 1 (emission is capped at line rate
    and path samples are >= the unloaded path, so FCT >= ideal FCT),
  * the incast row actually evicts (table pressure is exercised, not
    vacuous) while its conservation census still closes exactly,
  * the whole grid stays ONE compile, and a ``validate=True`` pass of
    the same batch (in-program finite + conservation + flow-census
    guards) is clean.

Every band is machine-independent (abs bounds / exact pins), so one
blessed section covers both JAX_ENABLE_X64 modes — the canary runs the
gate under both without re-blessing.

  PYTHONPATH=src python -m benchmarks.bench_flows              # full
  PYTHONPATH=src python -m benchmarks.bench_flows --smoke      # canary
  PYTHONPATH=src python -m benchmarks.bench_flows --smoke --check-baseline
  PYTHONPATH=src python -m benchmarks.bench_flows --smoke --update-baseline

``--check-baseline`` merges this bench's record into the PR's
``BENCH_<n>.json`` trajectory file under the ``bench_flows`` key.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from benchmarks import baseline_gate as BG
from repro.compile_cache import use_compile_cache
from repro.core import simulator as S
from repro.core import workloads
from repro.core.simulator import SimParams, make_batch, run_sweep
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

RESULTS = Path(__file__).resolve().parents[1] / "results"
OUT = RESULTS / "bench_flows.json"

#: flow-workload levels: (flow_size_dist, flow_arrival_rate) — rates
#: are per-rack per-tick arrival probabilities, chosen so "light" rows
#: drain (FCT frontier is meaningful) and "loaded" rows queue
LEVELS = {
    "web-light": ("websearch", 0.02),
    "web-loaded": ("websearch", 0.08),
    "dm-light": ("datamining", 0.02),
}

#: every scalar flow metric that must be EXACTLY zero at flow_mode=0
ZERO_FLOW_METRICS = tuple(
    ["flows_started", "flows_completed", "flows_evicted",
     "flow_evicted_frac", "fct_mean_us", "fct_slowdown_mean",
     "fct_p50_us", "fct_p99_us", "fct_slowdown_p50", "fct_slowdown_p99"]
    + [f"{stem}_{c}" for c in workloads.FLOW_CLASS_NAMES
       for stem in ("flows_completed", "fct_p50_us", "fct_p99_us",
                    "fct_slowdown_p50", "fct_slowdown_p99")])

#: machine-independent bands only — one bless covers both x64 modes
DEFAULT_BANDS = {
    # the flow engine must be inert at flow_mode=0 (bit-parity contract)
    "flows_zero_rows_max_metric": {"max_abs": 0.0},
    # exact flow conservation in EVERY row, eviction included — worst
    # absolute residual of started - (completed + evicted + in-table)
    "flows_conservation_resid": {"max_abs": 0.0},
    # FCT >= ideal FCT by construction, so slowdowns are >= 1; the
    # worst (smallest) p50 over every flow row pins it
    "flows_slowdown_p50_min": {"min_abs": 1.0},
    # the incast row must actually evict (table pressure exercised) and
    # every flow row must actually complete flows (percentiles are
    # measured, not vacuous)
    "flows_incast_evicted": {"min_abs": 1.0},
    "flows_completed_min": {"min_abs": 1.0},
    # gating keeps saving energy under flow-level traffic
    "flows_lcdc_savings_frac": {"min_abs": 0.05},
    # the whole grid is one vmapped batch: one compile, and the
    # validate=True pass (its own program) must come back clean
    "flows_traces": {"equal": True},
    "flows_validate_clean": {"equal": True},
}


def _grid_runs(site: FBSite):
    """(label, mode, SimParams) rows: flow workloads x {lcdc, base},
    two flow_mode=0 rows, and the incast/table-pressure row — all on
    one site so the grid is one ``make_batch`` compile."""
    spec = TRAFFIC_SPECS["fb_web"]
    rows = []
    # flow_mode=0: the rate-based engine, flow metrics must be inert
    for mode, gate in (("lcdc", True), ("base", False)):
        rows.append(("off", mode, SimParams(
            spec=spec, site=site, gating_enabled=gate, rate_scale=1.6)))
    for lvl, (dist, rate) in LEVELS.items():
        for mode, gate in (("lcdc", True), ("base", False)):
            rows.append((lvl, mode, SimParams(
                spec=spec, site=site, gating_enabled=gate, flow_mode=1,
                flow_size_dist=dist, flow_arrival_rate=rate)))
    # incast: 8-way bursts into an 8-slot table — forced eviction
    rows.append(("incast", "lcdc", SimParams(
        spec=spec, site=site, gating_enabled=True, flow_mode=1,
        flow_size_dist="websearch", flow_arrival_rate=0.3,
        incast_degree=8, flow_table_cap=8)))
    return rows


def _in_table(state, row: int, cap: int) -> float:
    """Flows still resident in row's usable table prefix at sweep end."""
    rem = np.asarray(state.ft_rem)[row]          # (R, FT)
    live = (rem > 0) & (np.arange(rem.shape[1])[None, :] < cap)
    return float(np.sum(live))


def bench_flows(args) -> dict:
    site = FBSite(n_clusters=2, racks_per_cluster=8, servers_per_rack=8,
                  csw_per_cluster=2, n_fc=2, csw_ring_links=4,
                  fc_ring_links=8) if args.smoke else FBSite()
    ticks = args.ticks or (2_000 if args.smoke else 20_000)
    chunk = max(1, ticks // 4)          # force a multi-chunk run
    rows = _grid_runs(site)
    batch = make_batch([(p, i) for i, (_, _, p) in enumerate(rows)])
    print(f"flow grid: {len(LEVELS)} workloads x {{lcdc, base}} "
          f"+ 2 off-rows + incast = {len(rows)} scenarios, "
          f"{ticks} ticks (chunk {chunk})")

    n0 = S.TRACE_COUNT
    t0 = time.time()
    res, state = run_sweep(batch, ticks, chunk_ticks=chunk,
                           return_state=True)
    t_grid = time.time() - t0
    traces = S.TRACE_COUNT - n0

    # exact flow-conservation census per row, eviction included
    resid = []
    for i, (_, _, p) in enumerate(rows):
        r = res[i]
        err = r["flows_started"] - (r["flows_completed"]
                                    + r["flows_evicted"]
                                    + _in_table(state, i, p.flow_table_cap))
        resid.append(abs(err))

    # the validate=True pass: same batch, in-program guards (a second
    # compile by design — the guard changes the chunk program)
    try:
        run_sweep(batch, min(ticks, 2 * chunk), chunk_ticks=chunk,
                  validate=True)
        validate_clean = 1
    except S.SweepValidationError as exc:
        print(f"validate=True pass FAILED: {exc}")
        validate_clean = 0

    by = {(lvl, mode): r for (lvl, mode, _), r in zip(rows, res)}
    zero_rows_max = max(
        abs(by["off", m][k])
        for m in ("lcdc", "base") for k in ZERO_FLOW_METRICS)
    flow_keys = [k for k in by if k[0] != "off"]
    slow_p50_min = min(by[k]["fct_slowdown_p50"] for k in flow_keys)
    completed_min = min(by[k]["flows_completed"] for k in flow_keys)

    print(f"\n{'level':10s} {'mode':5s} {'savings':>8s} {'started':>8s} "
          f"{'done':>7s} {'evict':>7s} {'sl_p50':>7s} {'sl_p99':>8s} "
          f"{'p99short':>9s} {'p99long':>10s}")
    frontier = []
    for lvl, mode, _ in rows:
        r = by[lvl, mode]
        print(f"{lvl:10s} {mode:5s} "
              f"{r['all_transceiver_savings_frac']:8.1%} "
              f"{r['flows_started']:8.0f} {r['flows_completed']:7.0f} "
              f"{r['flows_evicted']:7.0f} {r['fct_slowdown_p50']:7.2f} "
              f"{r['fct_slowdown_p99']:8.2f} "
              f"{r['fct_p99_us_short']:9.1f} {r['fct_p99_us_long']:10.1f}")
        frontier.append({
            "level": lvl, "mode": mode,
            "savings_frac": r["all_transceiver_savings_frac"],
            "flows_started": r["flows_started"],
            "flows_completed": r["flows_completed"],
            "flows_evicted": r["flows_evicted"],
            "fct_slowdown_p50": r["fct_slowdown_p50"],
            "fct_slowdown_p99": r["fct_slowdown_p99"],
            **{f"fct_p99_us_{c}": r[f"fct_p99_us_{c}"]
               for c in workloads.FLOW_CLASS_NAMES},
        })

    return {
        "ticks": ticks, "scenarios": len(rows),
        "t_grid_s": round(t_grid, 3),
        "flows_traces": traces,
        "flows_zero_rows_max_metric": zero_rows_max,
        "flows_conservation_resid": max(resid),
        "flows_slowdown_p50_min": slow_p50_min,
        "flows_incast_evicted": by["incast", "lcdc"]["flows_evicted"],
        "flows_completed_min": completed_min,
        "flows_lcdc_savings_frac": by["web-light", "lcdc"][
            "all_transceiver_savings_frac"],
        "flows_validate_clean": validate_clean,
        "frontier": frontier,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small site + short run, the CI flow canary")
    ap.add_argument("--check-baseline", action="store_true",
                    help="gate against the bench_flows baseline section")
    ap.add_argument("--update-baseline", action="store_true",
                    help="bless this run's values into baselines.json")
    args = ap.parse_args()

    results = {"smoke": args.smoke, "exec": S.execution_mode()}
    results.update(bench_flows(args))

    out = OUT.with_name("bench_flows_smoke.json") if args.smoke else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))
    print(f"written: {out}")

    mode = "smoke" if args.smoke else "full"
    sane = (results["flows_zero_rows_max_metric"] == 0.0
            and results["flows_conservation_resid"] == 0.0
            and results["flows_validate_clean"] == 1)
    if args.update_baseline:
        if not sane:
            raise SystemExit("refusing to bless baseline: this run "
                             "failed its own flow-model checks")
        bands = DEFAULT_BANDS
        prev = BG.load_section("bench_flows")
        if prev is not None and prev.get("mode") == mode:
            bands = {**DEFAULT_BANDS, **prev.get("bands", {})}
        missing = [k for k in bands if k not in results]
        if missing:
            raise SystemExit("refusing to bless baseline: banded "
                             f"metrics missing from this run: {missing}")
        BG.bless_section("bench_flows", mode,
                         {k: results[k] for k in bands}, bands)
        print(f"baseline blessed: {BG.BASELINE}")

    if args.check_baseline:
        baseline = BG.load_section("bench_flows")
        if baseline is None:
            raise SystemExit(f"no bench_flows baseline at {BG.BASELINE}; "
                             "bless one with --update-baseline and "
                             "commit it")
        if baseline.get("mode") != mode:
            raise SystemExit(
                f"baseline was blessed in {baseline.get('mode')!r} mode "
                f"but this run is {mode!r}; re-bless or match modes")
        print(f"\nbaseline gate ({BG.BASELINE.name}, mode={mode}):")
        fails = BG.check_bands(results, baseline)
        trajectory = BG.merge_trajectory("bench_flows", {
            "mode": mode, "gate": "failed" if fails else "passed",
            "exec": results["exec"],
            "checks": {k: results[k] for k in DEFAULT_BANDS},
            "frontier": results["frontier"],
            "timings_s": {"grid": results["t_grid_s"]},
        })
        print(f"trajectory record written: {trajectory}")
        if fails:
            raise SystemExit("baseline gate FAILED:\n  "
                             + "\n  ".join(fails))
        print("baseline gate passed")
    elif not sane:
        raise SystemExit(1)


if __name__ == "__main__":
    use_compile_cache()
    main()
