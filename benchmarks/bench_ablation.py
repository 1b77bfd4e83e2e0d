"""Ablation: watermark thresholds and the anti-flap dwell (paper Sec V:
"experimentally determined to balance energy savings with network
performance"). hi/lo/dwell are array-valued scenario knobs, so the whole
ablation grid runs as ONE batched sweep (one compile).

  PYTHONPATH=src python -m benchmarks.bench_ablation
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.compile_cache import use_compile_cache
from repro.core.simulator import SimParams, make_batch, run_sweep
from repro.core.traffic import TRAFFIC_SPECS

OUT = Path(__file__).resolve().parents[1] / "results" / "ablation.json"
TICKS = 30_000
TRACE = "fb_hadoop"


def main():
    spec = TRAFFIC_SPECS[TRACE]
    trials = [("always-on baseline", {"gating_enabled": False}),
              ("hi75/lo22 (paper)", {}),
              # threshold sensitivity
              ("hi50/lo22", {"hi": 0.50}),
              ("hi90/lo22", {"hi": 0.90}),
              ("hi75/lo10", {"lo": 0.10}),
              ("hi75/lo40", {"lo": 0.40})]
    # dwell ablation: flapping cost (DESIGN.md deviation note)
    trials += [(f"dwell={d}us", {"dwell": d})
               for d in (0, 64, 256, 1024, 4096)]

    res = run_sweep(make_batch(
        [(SimParams(spec=spec, **kw), 0) for _, kw in trials]), TICKS)
    base = res[0]
    print(f"trace={TRACE}, {TICKS} ticks, baseline latency "
          f"{base['mean_latency_us']:.2f} us "
          f"({len(trials)} scenarios, one compile)")
    rows = []
    for (tag, kw), r in zip(trials[1:], res[1:]):
        pen = r["mean_latency_us"] / base["mean_latency_us"] - 1
        rows.append({"tag": tag, **kw,
                     "savings": r["switch_energy_savings_frac"],
                     "penalty": pen})
        print(f"{tag:28s} savings={r['switch_energy_savings_frac']:.3f} "
              f"penalty={pen*100:+.1f}%")

    OUT.write_text(json.dumps(rows, indent=1))
    print(f"written: {OUT}")


if __name__ == "__main__":
    use_compile_cache()
    main()
