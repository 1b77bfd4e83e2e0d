"""Fig-1-style design comparison on the dynamic simulator: several
FBSite fabric shapes (same server population, different cluster / plane
/ core structure), each with LC/DC gating and the always-on baseline,
run through the hull-bucketing sweep planner — a handful of vmapped
compiles (``--max-compiles``, one per hull bucket, remainder tails
included) instead of one compile on the worst-case padded hull. The
buckets execute as an async pipeline (all chunk programs dispatched
before any result is fetched; ``--no-pipeline`` for strictly serial
buckets) with the device-resident fold's one-host-transfer-per-bucket
contract enforced.

This is the dynamic companion to topology.all_designs() (the paper's
static Fig 1 component-count power table, also printed for context):
instead of peak component power it reports what the watermark controller
actually achieves on each fabric shape under the same traffic.

  PYTHONPATH=src python -m benchmarks.bench_multi_site           # 20k us
  PYTHONPATH=src python -m benchmarks.bench_multi_site --smoke   # canary

--check additionally re-runs every scenario single-site and asserts the
PARITY_KEYS agree within --tol (the padding-is-inert contract, now per
bucket). --max-compiles 1 recovers the old single-hull path exactly.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.compile_cache import use_compile_cache
from repro.core import simulator as S
from repro.core.topology import FBSite, all_designs
from repro.core.traffic import TRAFFIC_SPECS

OUT = Path(__file__).resolve().parents[1] / "results" / \
    "bench_multi_site.json"

# same 128 racks x 48 servers, three fabric shapes: the Fig 2 default,
# a wide two-cluster build (fewer, fatter clusters), and a dense
# eight-cluster build (more, thinner clusters with 2 planes / 2 FCs)
SITES = {
    "fb_clos_4x32": FBSite(),
    "wide_2x64": FBSite(n_clusters=2, racks_per_cluster=64,
                        csw_per_cluster=4, n_fc=4),
    "dense_8x16": FBSite(n_clusters=8, racks_per_cluster=16,
                         csw_per_cluster=2, n_fc=2,
                         csw_ring_links=4, fc_ring_links=8),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=None)
    ap.add_argument("--trace", default="fb_hadoop",
                    choices=sorted(TRAFFIC_SPECS))
    ap.add_argument("--smoke", action="store_true",
                    help="short run, <1 min, for use as a CI canary")
    ap.add_argument("--check", action="store_true",
                    help="verify parity against single-site run_sweep")
    ap.add_argument("--tol", type=float, default=1e-3)
    ap.add_argument("--max-compiles", type=int, default=2,
                    help="planner hull-bucket budget (1 = old single-hull)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="run hull buckets strictly serially instead of "
                         "async-dispatching them all before fetching")
    args = ap.parse_args()

    # deliberately NOT a multiple of the chunk: the remainder tail must
    # ride the same compiled chunk program (live-tick mask)
    ticks = args.ticks or (1_000 if args.smoke else 20_000)
    chunk = 400 if args.smoke else 8_192

    spec = TRAFFIC_SPECS[args.trace]
    runs = [(S.SimParams(spec=spec, site=site, gating_enabled=g), 0)
            for site in SITES.values() for g in (True, False)]
    print(f"{len(SITES)} sites x {{lcdc, base}} = {len(runs)} scenarios, "
          f"trace={args.trace}, {ticks} ticks (chunk {chunk}), "
          f"max_compiles={args.max_compiles}")

    n0, h0 = S.TRACE_COUNT, S.HOST_TRANSFER_COUNT
    t0 = time.time()
    res, plan = S.run_sweep_planned(runs, ticks, chunk_ticks=chunk,
                                    max_compiles=args.max_compiles,
                                    return_plan=True,
                                    pipeline=not args.no_pipeline)
    wall = time.time() - t0
    traces = S.TRACE_COUNT - n0
    transfers = S.HOST_TRANSFER_COUNT - h0
    how = ("serial buckets" if args.no_pipeline else
           f"async pipeline, dispatch order {plan['dispatch_order']}")
    print(f"planned multi-site sweep: {wall:.2f} s ({how}), "
          f"step traces: {traces} "
          f"(contract: one per hull bucket = {plan['n_buckets']}, "
          f"remainder tails included), host transfers: {transfers} "
          f"(contract: one fold fetch per bucket)")
    if traces != plan["n_buckets"]:
        raise SystemExit("one-compile-per-bucket contract broken: "
                         f"{traces} traces for {plan['n_buckets']} buckets")
    if transfers > plan["n_buckets"]:
        raise SystemExit("one-transfer-per-bucket contract broken: "
                         f"{transfers} host transfers for "
                         f"{plan['n_buckets']} buckets")

    print(f"\n--- hull-bucket plan (padded-compute savings "
          f"{plan['savings_vs_single_hull_frac']:.1%} vs single hull) ---")
    for b in plan["buckets"]:
        print(f"hull {b['hull']:22s} x{b['n_scenarios']} scenarios  "
              f"waste {b['waste_frac']:6.1%}  indices {b['indices']}")

    print("\n--- static Fig 1 context (peak component power, kW) ---")
    for d in all_designs():
        kw = sum(d.network_power_w().values()) / 1e3
        print(f"{d.name:22s} {kw:8.1f} kW   ({d.notes})")

    print("\n--- dynamic LC/DC comparison (this sweep) ---")
    rows = []
    for i, (name, site) in enumerate(SITES.items()):
        lc, base = res[2 * i], res[2 * i + 1]
        pen = lc["mean_latency_us"] / base["mean_latency_us"] - 1.0
        rows.append({
            "site": name, "label": lc["label"],
            "gated_links": site.n_rsw_csw_links + site.n_csw_fc_links,
            "peak_transceiver_w": site.total_transceiver_power_w(),
            "switch_energy_savings_frac":
                lc["switch_energy_savings_frac"],
            "all_transceiver_savings_frac":
                lc["all_transceiver_savings_frac"],
            "transceiver_power_w": lc["transceiver_power_w"],
            "mean_latency_us": lc["mean_latency_us"],
            "latency_penalty": pen,
            "half_off_frac": lc["half_off_frac"],
        })
        print(f"{name:14s} savings={lc['switch_energy_savings_frac']:.3f} "
              f"(all-transceiver {lc['all_transceiver_savings_frac']:.3f}) "
              f"latency {lc['mean_latency_us']:6.2f} us "
              f"({pen*100:+.1f}%) half-off {lc['half_off_frac']:.0%}")

    worst_key, worst = None, 0.0
    if args.check:
        singles = [S.run_sweep(S.make_batch([run]), ticks,
                               chunk_ticks=chunk)[0] for run in runs]
        worst, worst_key = S.worst_parity(singles, res)
        ok = worst <= args.tol
        print(f"\nmax multi-vs-single-site rel diff: {worst:.2e} "
              f"[{worst_key}] {'OK' if ok else f'> tol {args.tol:g}'}")
        if not ok:
            raise SystemExit(1)

    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps({
        "smoke": args.smoke, "trace": args.trace, "ticks": ticks,
        "chunk_ticks": chunk, "scenarios": len(runs),
        "step_traces": traces, "host_transfers": transfers,
        "pipelined": not args.no_pipeline, "exec": S.execution_mode(),
        "wall_s": round(wall, 3),
        "checked": bool(args.check), "max_rel_diff": worst,
        "plan": plan,
        "sites": rows,
    }, indent=1))
    print(f"written: {OUT}")


if __name__ == "__main__":
    use_compile_cache()
    main()
