"""Fig-10-style savings-vs-delay frontier on the in-scan delay
distributions: a utilization x watermark grid of LC/DC scenarios (plus
one always-on baseline per utilization) runs as ONE batched sweep — a
single compile — and reports, per cell, the switch-tier energy savings
against the p50/p95/p99 packet-delay penalty and its attribution
(queueing vs STAGE_UP_DELAY wake stalls vs ring detours).

The paper's headline is "60% power saved at the cost of 6% higher
delay"; this bench reproduces that tradeoff as a frontier — more
aggressive watermarks / lower utilization buy more savings at a larger
delay-tail penalty — and checks the frontier is monotone-ish (delay
penalty rising with savings when sorted).

  PYTHONPATH=src python -m benchmarks.bench_delay            # full grid
  PYTHONPATH=src python -m benchmarks.bench_delay --smoke    # CI canary
  PYTHONPATH=src python -m benchmarks.bench_delay --check    # + assert

--smoke runs a 2x2 grid at 800 ticks (<1 min); --check exits nonzero if
the sweep re-traces or the frontier is grossly non-monotone.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.compile_cache import use_compile_cache
from repro.core import simulator as S
from repro.core.traffic import TRAFFIC_SPECS

OUT = Path(__file__).resolve().parents[1] / "results" / "bench_delay.json"

# (hi, lo) watermark pairs, most aggressive (latest stage-up, most
# savings) first; the default Sec V pair is in the middle
WATERMARKS = ((0.9, 0.4), (0.75, 0.22), (0.6, 0.15), (0.45, 0.1))


def frontier_monotone_frac(rows, key="penalty_p99"):
    """Fraction of adjacent pairs (sorted by savings) whose delay
    penalty does not decrease — 1.0 is a perfectly monotone frontier."""
    srt = sorted(rows, key=lambda r: r["switch_energy_savings_frac"])
    if len(srt) < 2:
        return 1.0
    ok = sum(b[key] >= a[key] - 0.02 for a, b in zip(srt, srt[1:]))
    return ok / (len(srt) - 1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--trace", default="fb_hadoop",
                    choices=sorted(TRAFFIC_SPECS))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid, <1 min, for use as a CI canary")
    ap.add_argument("--check", action="store_true",
                    help="assert one compile + monotone-ish frontier")
    args = ap.parse_args()

    if args.smoke:
        utils, wms = (0.6, 1.4), WATERMARKS[1:3]
        ticks = args.ticks or 800
    else:
        utils, wms = (0.4, 0.8, 1.2, 1.6), WATERMARKS
        ticks = args.ticks or 6_000

    spec = TRAFFIC_SPECS[args.trace]
    runs, cells = [], []
    for rs in utils:
        # one always-on baseline per utilization (watermarks are inert
        # with gating off; no need to repeat it per pair)
        runs.append((S.SimParams(spec=spec, gating_enabled=False,
                                 rate_scale=rs), 0))
        cells.append(("base", rs, None))
        for hi, lo in wms:
            runs.append((S.SimParams(spec=spec, gating_enabled=True,
                                     rate_scale=rs, hi=hi, lo=lo), 0))
            cells.append(("lcdc", rs, (hi, lo)))
    batch = S.make_batch(runs)
    print(f"{len(utils)} utilizations x {len(wms)} watermark pairs "
          f"(+{len(utils)} baselines) = {len(runs)} scenarios, "
          f"trace={args.trace}, {ticks} ticks, ONE compile")

    n0 = S.TRACE_COUNT
    t0 = time.time()
    res = S.run_sweep(batch, ticks)
    wall = time.time() - t0
    traces = S.TRACE_COUNT - n0
    print(f"sweep: {wall:.2f} s, step traces: {traces} (contract: 1)")

    base_by_util = {c[1]: r for c, r in zip(cells, res) if c[0] == "base"}
    rows = []
    print(f"\n{'util':>5} {'hi/lo':>9} {'savings':>8} {'p50':>7} "
          f"{'p99':>7} {'pen50':>7} {'pen99':>7} {'stall_us':>8} "
          f"{'queue_us':>8}")
    for cell, r in zip(cells, res):
        kind, rs, wm = cell
        if kind != "lcdc":
            continue
        b = base_by_util[rs]
        row = {
            "util": rs, "hi": wm[0], "lo": wm[1], "label": r["label"],
            "switch_energy_savings_frac": r["switch_energy_savings_frac"],
            "delay_p50_us": r["delay_p50_us"],
            "delay_p95_us": r["delay_p95_us"],
            "delay_p99_us": r["delay_p99_us"],
            "base_p50_us": b["delay_p50_us"],
            "base_p99_us": b["delay_p99_us"],
            "penalty_p50": r["delay_p50_us"] / b["delay_p50_us"] - 1.0,
            "penalty_p99": r["delay_p99_us"] / b["delay_p99_us"] - 1.0,
            "penalty_mean": (r["delay_mean_sampled_us"]
                             / b["delay_mean_sampled_us"] - 1.0),
            "delay_queue_us": r["delay_queue_us"],
            "delay_wake_stall_us": r["delay_wake_stall_us"],
            "delay_ring_us": r["delay_ring_us"],
            "wake_stall_frac": r["wake_stall_frac"],
        }
        rows.append(row)
        print(f"{rs:5.2f} {wm[0]:.2f}/{wm[1]:.2f} "
              f"{row['switch_energy_savings_frac']:8.3f} "
              f"{row['delay_p50_us']:7.2f} {row['delay_p99_us']:7.2f} "
              f"{row['penalty_p50']*100:+6.1f}% "
              f"{row['penalty_p99']*100:+6.1f}% "
              f"{row['delay_wake_stall_us']:8.4f} "
              f"{row['delay_queue_us']:8.3f}")

    mono = frontier_monotone_frac(rows)
    stall_ok = all(base_by_util[rs]["delay_wake_stall_us"] == 0.0
                   for rs in utils)
    print(f"\nfrontier monotone-ish (p99 penalty vs savings): "
          f"{mono:.0%} of adjacent pairs")
    print(f"baseline wake-stall attribution exactly 0: {stall_ok}")

    out = OUT.with_name("bench_delay_smoke.json") if args.smoke else OUT
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({
        "smoke": args.smoke, "trace": args.trace, "ticks": ticks,
        "scenarios": len(runs), "step_traces": traces,
        "wall_s": round(wall, 3), "frontier_monotone_frac": mono,
        "baseline_stall_zero": stall_ok, "rows": rows,
    }, indent=1))
    print(f"written: {out}")

    if args.check and (traces != 1 or not stall_ok or mono < 0.5):
        raise SystemExit(
            f"frontier check failed: traces={traces} (want 1), "
            f"stall_zero={stall_ok}, monotone_frac={mono:.2f} (want>=0.5)")


if __name__ == "__main__":
    use_compile_cache()
    main()
