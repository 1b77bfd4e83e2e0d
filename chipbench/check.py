"""The comparison that decides ``correct``.

A cell's traffic file names, under ``check.numbers``, the numbers to
compare, of two kinds; each is the widest over a sample of the window's
scenarios.

- ``metrics``: the widest relative gap, over the metrics listed, between
  what the timed path returned and what the plain reference computes
  for the same scenario, seed and length. A count (a metric in
  ``*_pkts`` or ``flows_*``) is taken relative to at least one packet
  or flow, so two runs that both count none agree.
- ``identity``: a conservation law, ``in == sum(out) + held``. ``in``
  and ``out`` are what the timed path returned; ``held`` (what the site
  still holds at the end, which the program does not report) is the
  reference's. The number is ``|in - sum(out) - held|`` relative to the
  reference's ``in`` (at least 1). A term is a metric, or a list of
  metrics whose product it is (a count reported as a share).
"""
from __future__ import annotations

import numpy as np


def is_count(metric: str) -> bool:
    return metric.endswith("_pkts") or metric.startswith("flows_")


def rel_gap(got: float, want: float, metric: str) -> float:
    floor = 1.0 if is_count(metric) else 1e-9
    gap = abs(float(got) - float(want)) / max(abs(float(want)), floor)
    return gap if np.isfinite(gap) else float("inf")


def term(r: dict, t) -> float:
    """A metric of one row, or the product of a list of them."""
    names = [t] if isinstance(t, str) else t
    return float(np.prod([float(r[m]) for m in names]))


def identity_gap(spec: dict, g: dict, w: dict) -> float:
    """How far the timed path's row ``g`` is from ``in == sum(out) +
    held``, with ``held`` from the reference's row ``w``."""
    try:
        rest = term(g, spec["in"]) - sum(term(g, t) for t in spec["out"])
    except KeyError:         # a term the timed path no longer reports
        return float("inf")
    gap = abs(rest - term(w, spec["held"])) / max(
        abs(term(w, spec["in"])), 1.0)
    return gap if np.isfinite(gap) else float("inf")


def numbers(check: dict, got: list, want: list) -> dict:
    """{number: (value, metric and row where it is widest)} of one
    sample: ``got`` and ``want`` are lists of metric dicts, row by row."""
    out = {}
    for name, spec in check["numbers"].items():
        worst, where = 0.0, None
        for i, (g, w) in enumerate(zip(got, want)):
            if "identity" in spec:
                gaps = [(identity_gap(spec["identity"], g, w), "identity")]
            else:
                # a metric the timed path no longer reports cannot agree
                gaps = [(rel_gap(g[m], w[m], m) if m in g else float("inf"),
                         m) for m in spec["metrics"]]
            for gap, m in gaps:
                if gap > worst:
                    worst, where = gap, f"row {i} {m}"
        out[name] = (worst, where)
    return out


def verdict(check: dict, nums: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit", "at"}}): correct when every
    number is finite and at most its limit."""
    table, ok = {}, True
    for name, (value, where) in nums.items():
        limit = check["numbers"][name]["limit"]
        # a number with no limit yet cannot pass
        ok &= limit is not None and bool(np.isfinite(value)
                                         and value <= limit)
        table[name] = {"value": value, "limit": limit, "at": where}
    return ok, table
