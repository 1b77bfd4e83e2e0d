"""Sweep-engine benchmark + the CI perf/parity regression gate.

Three sections, all written to results/ and all gated by the committed
baseline (benchmarks/baselines.json) under ``--check-baseline``:

1. serial vs batched — the full Fig 9/10 evaluation grid (every traffic
   trace x {LC/DC, always-on}) once through serial ``run_sim`` calls
   (re-trace + re-jit per scenario, the pre-sweep engine's behaviour)
   and once through one batched ``run_sweep``; reports scenarios/sec,
   scenario-ticks/sec, the wall-clock speedup, and the worst
   per-scenario metric divergence between the two paths.

2. device fold vs host fold — the bimodal acceptance mix on a
   multi-chunk run: the device-resident Kahan fold (one host transfer
   for the whole run) against the legacy per-chunk host fold; reports
   wall clock for both, the host-transfer counts (the device path MUST
   do exactly 1), and the worst metric divergence (<= 1e-6: Kahan
   compensation holds the cross-chunk float32 error at O(eps)).

3. hull-bucketing planner — the acceptance mix: a bimodal 6-site batch
   (3 small + 3 large fabrics) through the async-pipelined
   ``run_sweep_planned(max_compiles=2)`` vs the single-hull
   ``make_multi_site_batch`` path, multi-chunk; reports the modeled
   padded-compute savings (>= 30% required), the trace counts (one
   compile per hull bucket), the host-transfer count (<= 1 per
   bucket), and the worst metric divergence between planned and
   single-hull results. The bucketing report is also written to
   results/bench_planner_report.json (a CI build artifact).

4. checkpoint overhead — the same mix with cadenced durability
   snapshots (core/checkpoint.py) vs plain: the overhead ratio (the
   deferred-by-one snapshot writes must throttle, not serialize, the
   async chunk pipeline), the ``1 + n_checkpoints`` host-transfer pin,
   and the BIT-exact (rel diff == 0.0) parity of both the checkpointed
   run and a ``resume_sweep`` from its last mid-run snapshot.

Under ``--check-baseline`` the run additionally merges a
machine-readable perf-trajectory record into the repo root's
``BENCH_<n>.json`` (n = the PR index derived from CHANGES.md; speedups,
parity, bucket + host-transfer stats, execution mode, gate outcome —
under the ``bench_sweep`` key, alongside other benchmarks' records) so
future PRs have a bench trajectory to compare against.

  PYTHONPATH=src python -m benchmarks.bench_sweep            # full grid
  PYTHONPATH=src python -m benchmarks.bench_sweep --smoke    # <1 min canary
  PYTHONPATH=src python -m benchmarks.bench_sweep --smoke --check-baseline
  PYTHONPATH=src python -m benchmarks.bench_sweep --smoke --update-baseline

--check-baseline compares the run against this bench's SECTION of
benchmarks/baselines.json (shared machinery: baseline_gate.py) and
exits nonzero on any violated band: parity/savings/bucket-count gates
are machine-independent hard bounds, timing gates are generous ratios
to the blessed values (CI runners are noisy — the bands catch
order-of-magnitude regressions like a lost compile cache, not 10%
jitter). To bless a new baseline after an intentional perf change, run
with --update-baseline and commit the rewritten baselines.json (the
band definitions are preserved; only the blessed values move).
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

from benchmarks import baseline_gate as BG
from repro.compile_cache import use_compile_cache
from repro.core import checkpoint as CK
from repro.core import simulator as S
from repro.core.simulator import (CheckpointSpec, SimParams, grid_runs,
                                  make_batch, make_multi_site_batch,
                                  resume_sweep, run_sim, run_sweep,
                                  run_sweep_planned, worst_parity)
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

RESULTS = Path(__file__).resolve().parents[1] / "results"
OUT = RESULTS / "bench_sweep.json"
PLAN_OUT = RESULTS / "bench_planner_report.json"

# the acceptance-criteria mix: 3 small + 3 large fabrics whose shared
# hull would waste most of the compute on padding the small ones
BIMODAL_SITES = (
    FBSite(n_clusters=2, racks_per_cluster=4, servers_per_rack=8,
           csw_per_cluster=2, n_fc=2, csw_ring_links=4, fc_ring_links=8),
    FBSite(n_clusters=2, racks_per_cluster=5, servers_per_rack=8,
           csw_per_cluster=2, n_fc=2, csw_ring_links=4, fc_ring_links=8),
    FBSite(n_clusters=2, racks_per_cluster=6, servers_per_rack=8,
           csw_per_cluster=2, n_fc=2, csw_ring_links=4, fc_ring_links=8),
    FBSite(),                                  # the Fig 2 4x32 default
    FBSite(racks_per_cluster=28),
    FBSite(racks_per_cluster=24),
)

#: default tolerance bands, used when blessing a baseline from scratch.
#: *_abs bands are machine-independent hard bounds; *_frac_of_baseline
#: bands are generous ratios to the blessed value (CI noise tolerant).
DEFAULT_BANDS = {
    "speedup": {"min_frac_of_baseline": 0.25},
    "scen_ticks_per_s_batched": {"min_frac_of_baseline": 0.20},
    "t_batched_s": {"max_frac_of_baseline": 5.0},
    "max_rel_diff": {"max_abs": 1e-3},
    # device-resident fold: parity vs the host-fold reference is a hard
    # 1e-6 bound (compensated f32), the transfer count a hard pin, the
    # wall-clock ratio a generous machine band
    "fold_max_rel_diff": {"max_abs": 1e-6},
    "fold_host_transfers_device": {"equal": True},
    "fold_speedup": {"min_frac_of_baseline": 0.20},
    "planner_savings_frac": {"min_abs": 0.30,
                             "min_frac_of_baseline": 0.90},
    "planner_max_rel_diff": {"max_abs": 1e-3},
    "planner_n_buckets": {"equal": True},
    "planner_traces": {"equal": True},
    # async bucket pipeline: exactly one fold fetch per bucket
    "host_transfers_per_bucket": {"max_abs": 1.0},
    # durability: checkpointing only OBSERVES a run (bit-exact parity,
    # rel diff == 0.0 — no epsilon), the snapshot fetches are pinned at
    # 1 + n_checkpoints, and the deferred-by-one writes keep the
    # overhead a bounded ratio of the plain run's wall clock
    "ckpt_overhead_ratio": {"max_abs": 2.0},
    "ckpt_max_rel_diff": {"max_abs": 0.0},
    "ckpt_host_transfers": {"equal": True},
    "ckpt_n_checkpoints": {"equal": True},
}


def bench_serial_vs_batched(args) -> dict:
    if args.smoke:
        traces, seeds, scales = ("fb_hadoop", "university"), (0,), (1.0,)
        ticks = args.ticks or 800
    else:
        # the full Fig 9/10 evaluation matrix: every trace x {LC/DC,
        # always-on} x seeds x utilization (rate) scales
        traces, seeds, scales = (tuple(TRAFFIC_SPECS), (0, 1, 2, 3),
                                 (0.6, 1.0))
        ticks = args.ticks or 1_000
    runs = grid_runs(traces=traces, seeds=seeds, rate_scales=scales)
    n = len(runs)
    print(f"grid: {len(traces)} traces x {{lcdc, base}} x {len(seeds)} "
          f"seeds x {len(scales)} utilizations = {n} scenarios, "
          f"{ticks} ticks each")

    t0 = time.time()
    batched = run_sweep(make_batch(runs), ticks)
    t_batched = time.time() - t0
    print(f"batched run_sweep : {t_batched:8.2f} s  "
          f"({n / t_batched:6.2f} scen/s, "
          f"{n * ticks / t_batched:9.0f} scen-ticks/s)")

    t0 = time.time()
    serial = [run_sim(p, ticks, s) for p, s in runs]
    t_serial = time.time() - t0
    print(f"serial run_sim x{n}: {t_serial:8.2f} s  "
          f"({n / t_serial:6.2f} scen/s, "
          f"{n * ticks / t_serial:9.0f} scen-ticks/s)")

    speedup = t_serial / t_batched
    worst, worst_key = worst_parity(serial, batched)
    ok = worst <= args.tol
    print(f"speedup: {speedup:.2f}x  "
          f"(target >= 3x on the full grid)")
    print(f"max serial-vs-batched rel diff: {worst:.2e} "
          f"[{worst_key}] {'OK' if ok else f'> tol {args.tol:g}'}")
    return {
        "ticks": ticks, "scenarios": n,
        "t_serial_s": round(t_serial, 3),
        "t_batched_s": round(t_batched, 3),
        "speedup": round(speedup, 3),
        "scen_ticks_per_s_batched": round(n * ticks / t_batched, 1),
        "scen_ticks_per_s_serial": round(n * ticks / t_serial, 1),
        "max_rel_diff": worst, "max_rel_diff_key": worst_key,
        "metrics_match": ok,
    }


def _bimodal_runs():
    spec = TRAFFIC_SPECS["fb_hadoop"]
    return [(SimParams(spec=spec, site=site), i)
            for i, site in enumerate(BIMODAL_SITES)]


def bench_fold(args) -> dict:
    """Device-resident fold vs the legacy per-chunk host fold on a
    multi-chunk run of the acceptance mix (single hull, so the two
    paths time the exact same simulation work)."""
    # >= 15 chunks: the paths differ by a fixed per-chunk sync cost, so
    # enough boundaries are needed for the delta to clear timing noise
    # on a small CI machine (measured stable at this scale)
    ticks, chunk = (1_500, 100) if args.smoke else (8_000, 400)
    if args.ticks:
        ticks, chunk = args.ticks, max(1, args.ticks // 15)
    n_chunks = -(-ticks // chunk)
    batch = make_multi_site_batch(_bimodal_runs())
    print(f"\nfold: bimodal mix as one hull, {ticks} ticks in "
          f"{n_chunks} chunks of {chunk}")

    # warm both fold programs (same (hull, B, chunk) key as the timed
    # runs, so the timed section measures execution, not compile)
    run_sweep(batch, 2 * chunk, chunk_ticks=chunk)
    run_sweep(batch, 2 * chunk, chunk_ticks=chunk, fold="host")

    # best-of-4 reps, order swapped each rep: the first run after a
    # warmup carries allocator/cache noise and a fixed A-then-B order
    # systematically favors one path — both misread single-shot timing
    t_host = t_dev = float("inf")
    for rep in range(4):
        for which in (("host", "device") if rep % 2 == 0
                      else ("device", "host")):
            h0 = S.HOST_TRANSFER_COUNT
            t0 = time.time()
            if which == "host":
                host_res = run_sweep(batch, ticks, chunk_ticks=chunk,
                                     fold="host")
                t_host = min(t_host, time.time() - t0)
                transfers_host = S.HOST_TRANSFER_COUNT - h0
            else:
                dev_res = run_sweep(batch, ticks, chunk_ticks=chunk)
                t_dev = min(t_dev, time.time() - t0)
                transfers_dev = S.HOST_TRANSFER_COUNT - h0

    worst, worst_key = worst_parity(host_res, dev_res)
    ok = worst <= 1e-6 and transfers_dev == 1
    print(f"host fold   : {t_host:7.2f} s, {transfers_host} host "
          f"transfers ({n_chunks} chunks)")
    print(f"device fold : {t_dev:7.2f} s, {transfers_dev} host "
          f"transfer(s) (require exactly 1)")
    print(f"max device-vs-host-fold rel diff: {worst:.2e} [{worst_key}] "
          f"{'OK' if ok else '> 1e-6 or extra transfers'}")
    return {
        "fold_ticks": ticks, "fold_chunks": n_chunks,
        "t_fold_host_s": round(t_host, 3),
        "t_fold_device_s": round(t_dev, 3),
        "fold_speedup": round(t_host / t_dev, 3),
        "fold_host_transfers_host": transfers_host,
        "fold_host_transfers_device": transfers_dev,
        "fold_max_rel_diff": worst, "fold_max_rel_diff_key": worst_key,
        "fold_metrics_match": ok,
    }


def bench_planner(args) -> dict:
    """Planned (async-pipelined) vs single-hull on the bimodal
    acceptance mix, multi-chunk."""
    ticks = (args.ticks or 500) if args.smoke else (args.ticks or 4_000)
    chunk = max(1, ticks // 5)      # force a multi-chunk run
    runs = _bimodal_runs()
    print(f"\nplanner: bimodal mix, {len(runs)} sites "
          f"(3 small + 3 large), {ticks} ticks (chunk {chunk}), "
          f"max_compiles=2")

    # warm BOTH paths at the timed (hull, B, chunk) keys so the timed
    # section compares execution, not compile (the planned path would
    # otherwise pay its bucket compiles inside the timed region and the
    # trajectory record would report a bogus planner slowdown). The
    # one-compile-per-bucket contract is pinned on the COLD warmup run,
    # where the traces actually happen.
    run_sweep(make_multi_site_batch(runs), 2 * chunk, chunk_ticks=chunk)
    n0 = S.TRACE_COUNT
    run_sweep_planned(runs, 2 * chunk, max_compiles=2, chunk_ticks=chunk)
    traces_planned = S.TRACE_COUNT - n0

    n0 = S.TRACE_COUNT
    t0 = time.time()
    single = run_sweep(make_multi_site_batch(runs), ticks,
                       chunk_ticks=chunk)
    t_single = time.time() - t0
    traces_single = S.TRACE_COUNT - n0

    h0 = S.HOST_TRANSFER_COUNT
    t0 = time.time()
    planned, plan = run_sweep_planned(runs, ticks, max_compiles=2,
                                      chunk_ticks=chunk, return_plan=True)
    t_planned = time.time() - t0
    transfers_planned = S.HOST_TRANSFER_COUNT - h0

    worst, worst_key = worst_parity(single, planned)
    transfers_per_bucket = transfers_planned / max(plan["n_buckets"], 1)
    ok = worst <= args.tol and transfers_per_bucket <= 1.0
    savings = plan["savings_vs_single_hull_frac"]
    print(f"single hull : {t_single:7.2f} s, {traces_single} trace(s), "
          f"padded cost {plan['single_hull_cost']:.0f}")
    print(f"planned K=2 : {t_planned:7.2f} s, {traces_planned} trace(s), "
          f"{transfers_planned} host transfer(s) for "
          f"{plan['n_buckets']} buckets (require <= 1 per bucket), "
          f"dispatch order {plan['dispatch_order']}, "
          f"padded cost {plan['padded_cost']:.0f}")
    for b in plan["buckets"]:
        print(f"  hull {b['hull']:22s} x{b['n_scenarios']}  "
              f"waste {b['waste_frac']:6.1%}")
    print(f"padded-compute savings: {savings:.1%} (require >= 30%)")
    print(f"max planned-vs-single-hull rel diff: {worst:.2e} "
          f"[{worst_key}] {'OK' if ok else f'> tol {args.tol:g}'}")

    PLAN_OUT.parent.mkdir(parents=True, exist_ok=True)
    PLAN_OUT.write_text(json.dumps({
        "smoke": args.smoke, "ticks": ticks,
        "t_single_hull_s": round(t_single, 3),
        "t_planned_s": round(t_planned, 3),
        "max_rel_diff": worst, "max_rel_diff_key": worst_key,
        "plan": plan,
    }, indent=1))
    print(f"written: {PLAN_OUT}")
    return {
        "planner_ticks": ticks,
        "planner_chunk_ticks": chunk,
        "planner_savings_frac": savings,
        "planner_waste_frac": plan["waste_frac"],
        "planner_n_buckets": plan["n_buckets"],
        "planner_traces": traces_planned,
        "planner_host_transfers": transfers_planned,
        "host_transfers_per_bucket": transfers_per_bucket,
        "planner_dispatch_order": plan["dispatch_order"],
        "planner_max_rel_diff": worst,
        "planner_max_rel_diff_key": worst_key,
        "planner_metrics_match": ok,
        "t_single_hull_s": round(t_single, 3),
        "t_planned_s": round(t_planned, 3),
        "planner_fingerprint": plan["fingerprint"],
    }


def bench_checkpoint(args) -> dict:
    """Checkpointed vs plain device-fold run on the bimodal mix
    (multi-chunk): the cadenced snapshots (core/checkpoint.py) must
    only OBSERVE the run — bit-exact metric parity (rel diff == 0.0,
    no epsilon) for both the checkpointed run and a resume_sweep from
    its last mid-run snapshot — while the deferred-by-one writes keep
    the device pipeline busy (overhead gated as a ratio of the plain
    run) and the fetch count is pinned at exactly 1 + n_checkpoints."""
    ticks, chunk = (1_500, 100) if args.smoke else (8_000, 400)
    if args.ticks:
        ticks, chunk = args.ticks, max(1, args.ticks // 15)
    n_chunks = -(-ticks // chunk)
    every = max(1, n_chunks // 4)
    # snapshot boundaries: every cadence'th chunk boundary, final
    # boundary excluded (a finished run needs no checkpoint)
    n_ckpt = sum(1 for ci in range(1, n_chunks) if ci % every == 0)
    batch = make_multi_site_batch(_bimodal_runs())
    print(f"\ncheckpoint: bimodal mix as one hull, {ticks} ticks in "
          f"{n_chunks} chunks of {chunk}, snapshot every {every} "
          f"chunk(s) -> {n_ckpt} checkpoint(s)")

    # warm the shared fold program (same (hull, B, chunk) key)
    run_sweep(batch, 2 * chunk, chunk_ticks=chunk)

    with tempfile.TemporaryDirectory() as td:
        spec = CheckpointSpec(directory=Path(td), every_chunks=every,
                              tag="bench", keep=max(1, n_ckpt))
        # best-of-4 reps, order swapped each rep (same rationale as
        # bench_fold: allocator noise + order bias)
        t_plain = t_ckpt = float("inf")
        for rep in range(4):
            for which in (("plain", "ckpt") if rep % 2 == 0
                          else ("ckpt", "plain")):
                h0 = S.HOST_TRANSFER_COUNT
                t0 = time.time()
                if which == "plain":
                    plain_res = run_sweep(batch, ticks, chunk_ticks=chunk)
                    t_plain = min(t_plain, time.time() - t0)
                else:
                    ckpt_res = run_sweep(batch, ticks, chunk_ticks=chunk,
                                         checkpoint=spec)
                    t_ckpt = min(t_ckpt, time.time() - t0)
                    transfers_ckpt = S.HOST_TRANSFER_COUNT - h0

        # resume from the newest mid-run snapshot: must land on the
        # exact same metrics as the uninterrupted runs
        latest = CK.latest_checkpoint(Path(td), "bench")
        resumed = resume_sweep(latest)

    w_ckpt, k_ckpt = worst_parity(plain_res, ckpt_res)
    w_res, k_res = worst_parity(plain_res, resumed)
    worst, worst_key = max((w_ckpt, k_ckpt), (w_res, k_res))
    overhead = t_ckpt / t_plain
    ok = (worst == 0.0 and transfers_ckpt == 1 + n_ckpt)
    print(f"plain run    : {t_plain:7.2f} s")
    print(f"checkpointed : {t_ckpt:7.2f} s  ({overhead:.2f}x plain), "
          f"{transfers_ckpt} host transfer(s) "
          f"(require exactly 1 + {n_ckpt})")
    print(f"max ckpt/resume-vs-plain rel diff: {worst:.2e} [{worst_key}] "
          f"{'OK' if ok else '!= 0.0 or transfer pin broken'}")
    return {
        "ckpt_ticks": ticks, "ckpt_chunks": n_chunks,
        "ckpt_every_chunks": every,
        "ckpt_n_checkpoints": n_ckpt,
        "t_ckpt_plain_s": round(t_plain, 3),
        "t_ckpt_checkpointed_s": round(t_ckpt, 3),
        "ckpt_overhead_ratio": round(overhead, 3),
        "ckpt_host_transfers": transfers_ckpt,
        "ckpt_max_rel_diff": worst, "ckpt_max_rel_diff_key": worst_key,
        "ckpt_metrics_match": ok,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid, <1 min, for use as a perf canary")
    ap.add_argument("--tol", type=float, default=1e-3,
                    help="max allowed cross-path relative metric diff")
    ap.add_argument("--check-baseline", action="store_true",
                    help="gate this run against benchmarks/baselines.json")
    ap.add_argument("--update-baseline", action="store_true",
                    help="bless this run's values into baselines.json")
    args = ap.parse_args()

    results = {"smoke": args.smoke, "exec": S.execution_mode()}
    results.update(bench_serial_vs_batched(args))
    results.update(bench_fold(args))
    results.update(bench_planner(args))
    results.update(bench_checkpoint(args))

    out = OUT.with_name("bench_sweep_smoke.json") if args.smoke else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(results, indent=1))
    print(f"written: {out}")

    mode = "smoke" if args.smoke else "full"
    sane = (results["metrics_match"] and results["planner_metrics_match"]
            and results["fold_metrics_match"]
            and results["ckpt_metrics_match"])
    if args.update_baseline:
        # never bless a run that failed its own parity checks — a
        # broken run must not become the new reference
        if not sane:
            raise SystemExit("refusing to bless baseline: this run "
                             "failed its parity checks (max_rel_diff / "
                             "planner_max_rel_diff above --tol)")
        bands = DEFAULT_BANDS
        prev = BG.load_section("bench_sweep")
        if prev is not None and prev.get("mode") == mode:
            # keep hand-tuned bands for metrics that already had
            # one, but pick up newly introduced default bands too
            # (a re-bless must not silently drop a new gate)
            bands = {**DEFAULT_BANDS, **prev.get("bands", {})}
        missing = [k for k in bands if k not in results]
        if missing:
            raise SystemExit("refusing to bless baseline: banded "
                             f"metrics missing from this run: {missing}")
        BG.bless_section("bench_sweep", mode,
                         {k: results[k] for k in bands}, bands)
        print(f"baseline blessed: {BG.BASELINE}")

    if args.check_baseline:
        baseline = BG.load_section("bench_sweep")
        if baseline is None:
            raise SystemExit(f"no bench_sweep baseline at {BG.BASELINE}; "
                             "bless one with --update-baseline and "
                             "commit it")
        if baseline.get("mode") != mode:
            raise SystemExit(
                f"baseline was blessed in {baseline.get('mode')!r} mode "
                f"but this run is {mode!r}; re-bless or match modes")
        print(f"\nbaseline gate ({BG.BASELINE.name}, mode={mode}):")
        fails = BG.check_bands(results, baseline)
        # the perf-trajectory record: written even on gate failure (the
        # trajectory should record regressions, not hide them)
        record = {
            "mode": mode,
            "gate": "failed" if fails else "passed",
            "exec": results["exec"],
            "speedups": {
                "serial_vs_batched": results["speedup"],
                "fold_host_vs_device": results["fold_speedup"],
                "scen_ticks_per_s_batched":
                    results["scen_ticks_per_s_batched"],
            },
            "parity": {
                "serial_vs_batched": results["max_rel_diff"],
                "fold_device_vs_host": results["fold_max_rel_diff"],
                "planned_vs_single_hull": results["planner_max_rel_diff"],
            },
            "buckets": {
                "n_buckets": results["planner_n_buckets"],
                "traces": results["planner_traces"],
                "host_transfers_per_bucket":
                    results["host_transfers_per_bucket"],
                "dispatch_order": results["planner_dispatch_order"],
                "savings_frac": results["planner_savings_frac"],
                "waste_frac": results["planner_waste_frac"],
            },
            "durability": {
                "n_checkpoints": results["ckpt_n_checkpoints"],
                "host_transfers": results["ckpt_host_transfers"],
                "overhead_ratio": results["ckpt_overhead_ratio"],
                "max_rel_diff": results["ckpt_max_rel_diff"],
            },
            "timings_s": {
                "batched": results["t_batched_s"],
                "serial": results["t_serial_s"],
                "fold_device": results["t_fold_device_s"],
                "fold_host": results["t_fold_host_s"],
                "planned": results["t_planned_s"],
                "single_hull": results["t_single_hull_s"],
                "ckpt_plain": results["t_ckpt_plain_s"],
                "ckpt_checkpointed": results["t_ckpt_checkpointed_s"],
            },
        }
        trajectory = BG.merge_trajectory("bench_sweep", record)
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
