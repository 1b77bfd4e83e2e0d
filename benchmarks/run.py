"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``us_per_call`` is the wall time
of producing that figure's numbers (simulation/analysis cost); ``derived``
carries the figure's headline metrics next to the paper's claims. A
benchmark that raises still gets its row (``ERROR: ...``), the others
still run, and the run then exits nonzero naming every failed row.

  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

from repro.compile_cache import use_compile_cache


def main() -> None:
    rows = []

    def report(name, seconds, derived):
        rows.append((name, seconds * 1e6, derived))

    from benchmarks.bench_figures import ALL
    for bench in ALL:
        try:
            bench(report)
        except Exception as e:  # noqa: BLE001 - a bench must not kill the run
            rows.append((bench.__name__, 0.0, f"ERROR: {e}"))

    # roofline summary (full table via `python -m benchmarks.roofline`)
    try:
        from benchmarks.roofline import full_table
        import numpy as np
        t = [r for r in full_table() if "skipped" not in r]
        if t:
            dom = {}
            for r in t:
                dom[r["dominant"]] = dom.get(r["dominant"], 0) + 1
            rows.append(("roofline_summary", 0.0,
                         f"{len(t)} cells; dominant terms: {dom}; "
                         f"median MODEL/HLO="
                         f"{np.median([r['model_to_hlo_ratio'] for r in t]):.3f}"))
    except Exception as e:  # noqa: BLE001
        rows.append(("roofline_summary", 0.0, f"ERROR: {e}"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        d = str(derived).replace(",", ";")
        print(f"{name},{us:.1f},{d}")
    failed = [name for name, _, derived in rows
              if str(derived).startswith("ERROR")]
    if failed:
        raise SystemExit(f"failed benchmarks: {', '.join(failed)}")


if __name__ == "__main__":
    use_compile_cache()
    main()
