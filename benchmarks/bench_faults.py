"""Optical fault-injection benchmark: the savings-vs-availability
frontier, plus the CI correctness gate for the fault subsystem.

One batched sweep (a single compile: every fault knob is a ``Scenario``
array leaf) runs a grid of fault-severity levels x operating modes —
LC/DC gating with the connectivity-preserving fallback, LC/DC with the
fallback disabled (the ablation), and the always-on baseline — and
reports, per severity level, the energy savings the gating still
achieves against what the faults cost in availability: delivered
fraction, fault-drop fraction, connectivity-loss ticks, wake retries /
forced wakes, and the fault-stall delay attribution.

The run doubles as the fault-model regression gate (``--check-baseline``
against the ``bench_faults`` section of benchmarks/baselines.json, the
CI fault-canary job):

  * zero-fault rows report every fault metric as EXACTLY zero (the
    fault model must be inert when disabled — the bit-parity contract),
  * packet conservation holds with the fault-drop bin included
    (injected == delivered + drops + fault_drops + in-flight),
  * with the fallback enabled no valid switch ever loses its last
    usable uplink (conn_loss_ticks == 0); with it disabled, it does,
  * with gating disabled the fault-stall attribution and wake
    retry/fallback counters are exactly zero (stage-up never happens),
  * the whole grid stays ONE compile, and a ``validate=True`` pass of
    the same batch (in-program finite + conservation guards) is clean.

Every band is machine-independent (abs bounds / exact pins), so one
blessed section covers both JAX_ENABLE_X64 modes — the canary runs the
gate under both without re-blessing.

  PYTHONPATH=src python -m benchmarks.bench_faults             # full
  PYTHONPATH=src python -m benchmarks.bench_faults --smoke     # canary
  PYTHONPATH=src python -m benchmarks.bench_faults --smoke --check-baseline
  PYTHONPATH=src python -m benchmarks.bench_faults --smoke --update-baseline

``--check-baseline`` merges this bench's record into the PR's
``BENCH_<n>.json`` trajectory file under the ``bench_faults`` key.
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
from repro.core.simulator import SimParams, make_batch, run_sweep
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

RESULTS = Path(__file__).resolve().parents[1] / "results"
OUT = RESULTS / "bench_faults.json"

#: fault-severity levels:
#:   (wake_fail_prob, wake_jitter_frac, link_mtbf_ticks, repair_ticks)
LEVELS = {
    "none": (0.0, 0.0, 0, 0),
    "mild": (0.05, 0.25, 50_000, 200),
    "harsh": (0.30, 0.50, 5_000, 400),
}

#: every fault metric that must be EXACTLY zero when the knobs are zero
ZERO_FAULT_METRICS = (
    "fault_drop_frac", "fault_dropped_pkts", "wake_retries",
    "forced_wakes", "conn_loss_ticks", "link_fault_frac",
    "delay_fault_stall_us", "fault_stall_frac",
)

#: machine-independent bands only — one bless covers both x64 modes
DEFAULT_BANDS = {
    # the fault model must be inert at zero knobs (bit-parity contract)
    "faults_zero_rows_max_metric": {"max_abs": 0.0},
    # conservation with the fault-drop bin, worst row over the grid
    "faults_conservation_rel_err": {"max_abs": 1e-3},
    # min-connectivity invariant: fallback on -> no switch ever loses
    # its last usable uplink; the no-fallback ablation must actually
    # lose connectivity under harsh faults (else the invariant test is
    # vacuous)
    "faults_fallback_conn_loss_ticks": {"max_abs": 0.0},
    "faults_nofb_conn_loss_ticks": {"min_abs": 1.0},
    # gating disabled -> stage-up never happens: no retries, no forced
    # wakes, no fault-stall attribution
    "faults_gating_off_stall": {"max_abs": 0.0},
    # harsh faults degrade availability but must not collapse it
    "faults_harsh_delivered_frac": {"min_abs": 0.5},
    # the whole grid is one vmapped batch: one compile, and the
    # validate=True pass (its own program) must come back clean
    "faults_traces": {"equal": True},
    "faults_validate_clean": {"equal": True},
}


def _grid_runs(site: FBSite):
    """(label, SimParams, seed) rows: severity levels x operating
    modes, all on one site so the grid is one ``make_batch`` compile."""
    spec = TRAFFIC_SPECS["fb_hadoop"]
    rows = []
    for lvl, (wfp, wjf, mtbf, rep) in LEVELS.items():
        # rate_scale 1.6: enough load that watermark-driven stage churn
        # actually happens — wake events are what the transient-failure
        # and jitter knobs act on; at 1.0 the stage barely moves and
        # the wake-retry path would go unexercised
        knobs = dict(rate_scale=1.6, wake_fail_prob=wfp,
                     wake_jitter_frac=wjf, link_mtbf_ticks=mtbf,
                     repair_ticks=rep)
        rows.append((lvl, "lcdc", SimParams(
            spec=spec, site=site, gating_enabled=True, **knobs)))
        rows.append((lvl, "lcdc-nofb", SimParams(
            spec=spec, site=site, gating_enabled=True,
            fault_fallback=False, **knobs)))
        rows.append((lvl, "base", SimParams(
            spec=spec, site=site, gating_enabled=False, **knobs)))
    return rows


def bench_faults(args) -> dict:
    site = FBSite(n_clusters=2, racks_per_cluster=8, servers_per_rack=8,
                  csw_per_cluster=2, n_fc=2, csw_ring_links=4,
                  fc_ring_links=8) if args.smoke else FBSite()
    ticks = args.ticks or (2_000 if args.smoke else 20_000)
    chunk = max(1, ticks // 4)          # force a multi-chunk run
    rows = _grid_runs(site)
    # per-row seeds keep every scenario label unique in the batch
    batch = make_batch([(p, i) for i, (_, _, p) in enumerate(rows)])
    print(f"fault grid: {len(LEVELS)} severity levels x "
          f"{{lcdc, lcdc-nofb, base}} = {len(rows)} scenarios, "
          f"{ticks} ticks (chunk {chunk})")

    n0 = S.TRACE_COUNT
    t0 = time.time()
    res, state = run_sweep(batch, ticks, chunk_ticks=chunk,
                           return_state=True)
    t_grid = time.time() - t0
    traces = S.TRACE_COUNT - n0

    # conservation per row, fault-drop bin included (state-level audit)
    cons = []
    for i, r in enumerate(res):
        in_flight = sum(float(np.sum(np.asarray(q)[i]))
                        for q in (state.rsw_q, state.csw_up_q,
                                  state.csw_down_q, state.fc_down_q))
        inj = r["injected_pkts"]
        err = inj - (r["delivered_pkts"] + r["drop_frac"] * inj
                     + r["fault_dropped_pkts"] + in_flight)
        cons.append(abs(err) / max(inj, 1e-9))

    # the validate=True pass: same batch, in-program guards (this is a
    # second compile by design — the guard changes the chunk program)
    try:
        run_sweep(batch, min(ticks, 2 * chunk), chunk_ticks=chunk,
                  validate=True)
        validate_clean = 1
    except S.SweepValidationError as exc:
        print(f"validate=True pass FAILED: {exc}")
        validate_clean = 0

    by = {(lvl, mode): r for (lvl, mode, _), r in zip(rows, res)}
    zero_rows_max = max(
        abs(by["none", m][k])
        for m in ("lcdc", "lcdc-nofb", "base") for k in ZERO_FAULT_METRICS)
    gating_off_stall = max(
        abs(by[lvl, "base"][k])
        for lvl in LEVELS
        for k in ("fault_stall_frac", "delay_fault_stall_us",
                  "wake_retries", "forced_wakes"))
    fb_conn = max(by[lvl, "lcdc"]["conn_loss_ticks"] for lvl in LEVELS)
    nofb_conn = by["harsh", "lcdc-nofb"]["conn_loss_ticks"]

    print(f"\n{'level':8s} {'mode':10s} {'savings':>8s} {'deliv':>7s} "
          f"{'fdrop':>8s} {'connloss':>8s} {'retries':>8s} "
          f"{'forced':>7s} {'fstall_us':>9s}")
    frontier = []
    for lvl in LEVELS:
        for mode in ("lcdc", "lcdc-nofb", "base"):
            r = by[lvl, mode]
            print(f"{lvl:8s} {mode:10s} "
                  f"{r['all_transceiver_savings_frac']:8.1%} "
                  f"{r['delivered_frac']:7.3f} "
                  f"{r['fault_drop_frac']:8.2e} "
                  f"{r['conn_loss_ticks']:8.0f} {r['wake_retries']:8.0f} "
                  f"{r['forced_wakes']:7.0f} "
                  f"{r['delay_fault_stall_us']:9.4f}")
            frontier.append({
                "level": lvl, "mode": mode,
                "savings_frac": r["all_transceiver_savings_frac"],
                "delivered_frac": r["delivered_frac"],
                "fault_drop_frac": r["fault_drop_frac"],
                "conn_loss_ticks": r["conn_loss_ticks"],
                "wake_retries": r["wake_retries"],
                "forced_wakes": r["forced_wakes"],
                "delay_fault_stall_us": r["delay_fault_stall_us"],
                "link_fault_frac": r["link_fault_frac"],
            })

    return {
        "ticks": ticks, "scenarios": len(rows), "t_grid_s": round(t_grid, 3),
        "faults_traces": traces,
        "faults_zero_rows_max_metric": zero_rows_max,
        "faults_conservation_rel_err": max(cons),
        "faults_fallback_conn_loss_ticks": fb_conn,
        "faults_nofb_conn_loss_ticks": nofb_conn,
        "faults_gating_off_stall": gating_off_stall,
        "faults_harsh_delivered_frac": by["harsh", "lcdc"][
            "delivered_frac"],
        "faults_validate_clean": validate_clean,
        "frontier": frontier,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small site + short run, the CI fault canary")
    ap.add_argument("--check-baseline", action="store_true",
                    help="gate against the bench_faults baseline section")
    ap.add_argument("--update-baseline", action="store_true",
                    help="bless this run's values into baselines.json")
    args = ap.parse_args()

    results = {"smoke": args.smoke, "exec": S.execution_mode()}
    results.update(bench_faults(args))

    out = OUT.with_name("bench_faults_smoke.json") if args.smoke else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))
    print(f"written: {out}")

    mode = "smoke" if args.smoke else "full"
    sane = (results["faults_zero_rows_max_metric"] == 0.0
            and results["faults_conservation_rel_err"] <= 1e-3
            and results["faults_validate_clean"] == 1)
    if args.update_baseline:
        if not sane:
            raise SystemExit("refusing to bless baseline: this run "
                             "failed its own fault-model checks")
        bands = DEFAULT_BANDS
        prev = BG.load_section("bench_faults")
        if prev is not None and prev.get("mode") == mode:
            bands = {**DEFAULT_BANDS, **prev.get("bands", {})}
        missing = [k for k in bands if k not in results]
        if missing:
            raise SystemExit("refusing to bless baseline: banded "
                             f"metrics missing from this run: {missing}")
        BG.bless_section("bench_faults", mode,
                         {k: results[k] for k in bands}, bands)
        print(f"baseline blessed: {BG.BASELINE}")

    if args.check_baseline:
        baseline = BG.load_section("bench_faults")
        if baseline is None:
            raise SystemExit(f"no bench_faults baseline at {BG.BASELINE}; "
                             "bless one with --update-baseline and "
                             "commit it")
        if baseline.get("mode") != mode:
            raise SystemExit(
                f"baseline was blessed in {baseline.get('mode')!r} mode "
                f"but this run is {mode!r}; re-bless or match modes")
        print(f"\nbaseline gate ({BG.BASELINE.name}, mode={mode}):")
        fails = BG.check_bands(results, baseline)
        trajectory = BG.merge_trajectory("bench_faults", {
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
