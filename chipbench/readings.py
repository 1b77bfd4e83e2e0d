#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip.

  python3 chipbench/readings.py --workload <name> --seeds 1,2,3 \\
      --control-seeds 1,2,3 [--out FILE]

For every seed of ``--seeds``: one sweep of the cell through the timed
path (``run_sweep_planned`` at the cell's own size), the sample the
benchmark would draw from that seed, and each compared number between
that sample and the float32 reference. For every seed of
``--control-seeds``: the same numbers between the reference computed in
bfloat16 (the control: the next precision below the configuration's)
and the float32 reference. The lower reading of a number is the largest
over the program's seeds, the upper the smallest over the control's.
Prints one JSON line per seed and a summary line; ``--out`` also writes
them to a file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))
sys.path.insert(0, str(BENCH.parent / "src"))

from chipbench import check, run  # noqa: E402


def seeds_arg(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, default=[])
    ap.add_argument("--control-seeds", type=seeds_arg, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.Cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"readings: {cell.name} needs {cell.chips} TPU chips",
              file=sys.stderr)
        return 1
    eng = run.Engine(cell)
    ck = cell.traffic["check"]
    lines = []

    def emit(rec):
        lines.append(rec)
        print(json.dumps(rec), flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        res = eng.sweep(eng.runs(seed, 1), cell.n_ticks)
        t1 = time.perf_counter()
        window = {"sweeps": [{"seeds": [s for _, s in eng.runs(seed, 1)],
                              "results": res}]}
        _, idx = run.sample(cell, window, seed)
        rows = [cell.rows[i] for i in idx]
        seeds = [window["sweeps"][0]["seeds"][i] for i in idx]
        want = cell.reference().reference_metrics(cell.cfg, rows, seeds,
                                                  cell.n_ticks)
        t2 = time.perf_counter()
        nums = check.numbers(ck, [res[i] for i in idx], want)
        emit({"kind": "program", "seed": seed, "sweep_s": t1 - t0,
              "reference_s": t2 - t1,
              "numbers": {k: v for k, (v, _) in nums.items()},
              "at": {k: w for k, (_, w) in nums.items()}})
    for seed in args.control_seeds:
        seeds_all = [s for _, s in eng.runs(seed, 1)]
        _, idx = run.sample(cell, {"sweeps": [None]}, seed)
        rows = [cell.rows[i] for i in idx]
        seeds = [seeds_all[i] for i in idx]
        ref = cell.reference()
        t0 = time.perf_counter()
        want = ref.reference_metrics(cell.cfg, rows, seeds, cell.n_ticks)
        got = ref.reference_metrics(cell.cfg, rows, seeds, cell.n_ticks,
                                    dtype=jnp.bfloat16)
        nums = check.numbers(ck, got, want)
        emit({"kind": "control", "seed": seed,
              "seconds": time.perf_counter() - t0,
              "numbers": {k: v for k, (v, _) in nums.items()},
              "at": {k: w for k, (_, w) in nums.items()}})
    summary = {"kind": "summary", "workload": cell.name,
               "device_kind": devs[0].device_kind}
    for name in ck["numbers"]:
        prog = [r["numbers"][name] for r in lines if r["kind"] == "program"]
        ctl = [r["numbers"][name] for r in lines if r["kind"] == "control"]
        summary[name] = {"lower": max(prog) if prog else None,
                         "upper": min(ctl) if ctl else None,
                         "program": prog, "control": ctl}
    emit(summary)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("".join(json.dumps(r) + "\n"
                                          for r in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
