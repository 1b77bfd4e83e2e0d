"""Flow-level workload models: heavy-tailed DCN flow-size distributions.

The paper evaluates aggregate packet delay, but modern DCN comparisons
(pFabric, PULSE, the optical-switching surveys) rank architectures on
per-flow-size-class FCT slowdown. This module holds the flow-size CDFs
and the in-scan sampling machinery the simulator's flow engine
(``flow_mode=1``, core/simulator.py) draws from:

* ``websearch``  — the web-search workload of the DCTCP/pFabric papers:
  ~60% of flows under 100 KB but >95% of the *bytes* in flows over 1 MB.
* ``datamining`` — the data-mining workload of VL2/pFabric: ~80% of
  flows under 10 KB with a far heavier tail (up to ~800 MB), so mice
  dominate counts even more and elephants dominate bytes even more.

Both CDFs are stored as (size_pkts, P(size <= s)) anchor tables in
PACKETS (1250 B per packet, the simulator's fluid unit, ~1500 B MTU
minus headers) and sampled by inverse transform with log-linear
interpolation between anchors — sizes are integral (ceil) and >= 1.

Everything here is pure jnp on f32 (bit-exact across x64 modes) and
table-driven: ``CDF_SIZE_PKTS``/``CDF_PROB`` stack every distribution
into one (D, P) constant pair so the *distribution index* can be a
traced scenario knob — one compiled program samples any mix of
distributions across the sweep batch.

Size classes follow the pFabric reporting convention: ``short``
(< ~100 KB), ``medium``, ``long`` (> ~10 MB); edges in
``FLOW_CLASS_EDGES_PKTS``. ``ideal_fct_us`` is the idealized baseline
FCT (line-rate serialization + unloaded path latency) against which
the simulator reports slowdowns.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import constants as C

#: distribution names in CDF table order; the Scenario ``flow_dist``
#: knob is an index into this tuple
FLOW_DIST_NAMES = ("websearch", "datamining")

# CDF anchors as (size_pkts, cum_prob). Published anchor points of the
# DCTCP web-search and VL2 data-mining distributions, converted from
# bytes at 1250 B/pkt and lightly coarsened (log-linear interpolation
# between anchors reproduces the published curves to well under the
# simulator's bin resolution). A repeated size with increasing prob
# encodes an atom (datamining: half of all flows are a single packet).
_WEBSEARCH_CDF = (
    (1, 0.00), (7, 0.15), (15, 0.20), (22, 0.30), (39, 0.40),
    (62, 0.53), (155, 0.60), (779, 0.70), (1557, 0.80),
    (3893, 0.90), (7786, 0.97), (23360, 1.00),
)
_DATAMINING_CDF = (
    (1, 0.00), (1, 0.50), (2, 0.60), (4, 0.70), (8, 0.80),
    (312, 0.90), (2462, 0.95), (77867, 0.99), (778667, 1.00),
)


def _stack_cdfs(*tables):
    """Pad anchor tables to one (D, P) pair of f32 constants (repeating
    each table's last anchor, which is inert under interpolation)."""
    width = max(len(t) for t in tables)
    sizes, probs = [], []
    for t in tables:
        t = tuple(t) + (t[-1],) * (width - len(t))
        sizes.append([s for s, _ in t])
        probs.append([p for _, p in t])
    return (np.asarray(sizes, np.float32), np.asarray(probs, np.float32))


#: (D, P) stacked anchor tables, row order == FLOW_DIST_NAMES
CDF_SIZE_PKTS, CDF_PROB = _stack_cdfs(_WEBSEARCH_CDF, _DATAMINING_CDF)

#: short/medium/long class edges in packets (~100 KB / ~10 MB at
#: 1250 B/pkt) — the pFabric reporting buckets
FLOW_CLASS_EDGES_PKTS = (80, 8000)
FLOW_CLASS_NAMES = ("short", "medium", "long")


def sample_flow_size_pkts(u, dist):
    """Inverse-CDF flow sizes: uniforms ``u`` (any shape, in [0, 1))
    -> integral packet counts (f32, >= 1) from distribution index
    ``dist`` (a scalar int into FLOW_DIST_NAMES; traced is fine — the
    simulator passes the Scenario knob).

    Log-linear interpolation between anchors: within segment
    [(s0, p0), (s1, p1)] the size is s0 * (s1/s0)**frac with
    frac = (u - p0)/(p1 - p0) — monotone in u within and across
    segments, so the sampler itself is monotone (the hypothesis
    property tests/test_flows.py pins). Pure f32, no host branching.
    No gathers, for the TPU: each table lookup is a one-hot select
    summed over the small static axis it indexes (one non-zero term,
    every table value finite and >= +0.0, so the sum is exact).
    """
    ndist, npts = CDF_PROB.shape
    row = (jnp.asarray(dist) == jnp.arange(ndist))[:, None]     # (D, 1)
    size_tab = jnp.sum(jnp.where(row, CDF_SIZE_PKTS, 0.0), axis=0)  # (P,)
    prob_tab = jnp.sum(jnp.where(row, CDF_PROB, 0.0), axis=0)
    u = jnp.asarray(u, jnp.float32)
    # segment index: the last anchor with prob <= u (atoms — repeated
    # sizes — collapse to a zero-length segment whose interp is exact)
    seg = jnp.clip(jnp.sum((u[..., None] >= prob_tab).astype(jnp.int32),
                           axis=-1) - 1, 0, npts - 2)
    anchor = jnp.arange(npts)
    at_lo = seg[..., None] == anchor
    at_hi = (seg + 1)[..., None] == anchor

    def pick(mask, tab):
        return jnp.sum(jnp.where(mask, tab, 0.0), axis=-1)

    lo_s, hi_s = pick(at_lo, size_tab), pick(at_hi, size_tab)
    lo_p, hi_p = pick(at_lo, prob_tab), pick(at_hi, prob_tab)
    frac = jnp.clip((u - lo_p) / jnp.maximum(hi_p - lo_p, 1e-9),
                    0.0, 1.0)
    size = lo_s * (hi_s / lo_s) ** frac
    return jnp.maximum(jnp.ceil(size), 1.0)


def flow_size_class(size_pkts):
    """Size-class index (0=short, 1=medium, 2=long) of integral packet
    counts; edges from FLOW_CLASS_EDGES_PKTS, half-open-left (a flow
    exactly at an edge belongs to the smaller class)."""
    lo, hi = FLOW_CLASS_EDGES_PKTS
    s = jnp.asarray(size_pkts)
    return ((s > lo).astype(jnp.int32) + (s > hi).astype(jnp.int32))


def ideal_fct_us(size_pkts, base_path_us):
    """Idealized FCT baseline: unloaded path latency + line-rate
    serialization (C.FLOW_LINE_RATE_PPT pkts/tick, 1 us ticks). The
    denominator of the simulator's FCT slowdown metrics; by
    construction every measured FCT >= this (per-tick flow emission is
    capped at the line rate and path samples are >= the unloaded
    path), so slowdowns are >= 1."""
    return (jnp.asarray(base_path_us, jnp.float32)
            + jnp.asarray(size_pkts, jnp.float32)
            / C.FLOW_LINE_RATE_PPT * C.TICK_US)
