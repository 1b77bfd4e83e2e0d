"""Flow-level workload engine (flow_mode=1): bit-parity of flow_mode=0
against the committed pre-flow golden results, flow-knob inertness, the
exact flow-conservation census under gating + faults, table-overflow
eviction accounting, the sampler monotonicity property and its
bit-for-bit match with a NumPy inverse CDF, and the one-trace /
one-transfer pins on a flow batch."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import constants as C
from repro.core import simulator as S
from repro.core import workloads
from repro.core.topology import FBSite
from repro.core.traffic import TRAFFIC_SPECS

GOLDEN = Path(__file__).with_name("data") / "preflow_golden.json"
# the golden capture's site/ticks (tests/data/preflow_golden.json
# "config"): two clusters so inter traffic exercises the CSW/FC tiers
SITE = FBSite(n_clusters=2, racks_per_cluster=8, servers_per_rack=8,
              csw_per_cluster=2, n_fc=2, csw_ring_links=4, fc_ring_links=8)
HARSH = dict(wake_fail_prob=0.30, wake_jitter_frac=0.50,
             link_mtbf_ticks=5_000.0, repair_ticks=400)
TICKS, CHUNK = 1_000, 250


def _params(spec="fb_web", **kw):
    return S.SimParams(spec=TRAFFIC_SPECS[spec], site=SITE, **kw)


def _golden_runs():
    """The exact (SimParams, seed) rows of the pre-flow golden capture
    (labels fb_hadoop|lcdc|x1.6|s8, fb_hadoop|base|x1.6|s9,
    fb_web|lcdc|x1|s3)."""
    return [(_params("fb_hadoop", gating_enabled=True, rate_scale=1.6), 8),
            (_params("fb_hadoop", gating_enabled=False, rate_scale=1.6), 9),
            (_params("fb_web", gating_enabled=True), 3)]


# ---- flow_mode=0 bit-parity vs the pre-flow engine ----------------------

def test_flow_mode0_bit_identical_to_preflow_golden():
    """The tentpole contract: with flow_mode=0 (the default) every
    metric — histograms included — is BIT-identical to the engine as it
    existed before the flow subsystem, in the current x64 mode."""
    g = json.loads(GOLDEN.read_text())
    cfg = g["config"]
    batch = S.make_batch(_golden_runs())
    res = S.run_sweep(batch, cfg["ticks"], chunk_ticks=cfg["chunk_ticks"])
    rows = g["results_x64"] if jax.config.jax_enable_x64 else g["results"]
    assert [r["label"] for r in rows] == list(batch.labels)
    for want, got in zip(rows, res):
        for k, v in want.items():
            if k in ("label", "trace", "gating", "ticks"):
                continue
            assert k in got, k
            if isinstance(v, list):
                np.testing.assert_array_equal(
                    np.asarray(got[k]), np.asarray(v), err_msg=k)
            else:
                assert got[k] == v, (k, got[k], v)


def test_flow_knobs_inert_at_mode0():
    """At flow_mode=0 the other four flow knobs must not perturb ANY
    result bit (same seed, wildly different flow knobs)."""
    plain = _params("fb_hadoop", gating_enabled=True, rate_scale=1.6)
    weird = _params("fb_hadoop", gating_enabled=True, rate_scale=1.6,
                    flow_arrival_rate=0.7, flow_size_dist="datamining",
                    incast_degree=C.MAX_INCAST_DEGREE, flow_table_cap=3)
    res = S.run_sweep(S.make_batch([(plain, 8), (weird, 8)]),
                      TICKS, chunk_ticks=CHUNK)
    for k, v in res[0].items():
        if isinstance(v, list):
            np.testing.assert_array_equal(
                np.asarray(res[1][k]), np.asarray(v), err_msg=k)
        else:
            assert res[1][k] == v, k


# ---- the flow engine itself ---------------------------------------------

@pytest.fixture(scope="module")
def flow_results():
    """One sweep over the canonical flow modes: light websearch under
    LC/DC, light datamining always-on, websearch under LC/DC + harsh
    optical faults, and the incast/table-pressure row."""
    rows = {
        "web": _params(flow_mode=1, flow_arrival_rate=0.05),
        "dm_base": _params(flow_mode=1, flow_arrival_rate=0.05,
                           flow_size_dist="datamining",
                           gating_enabled=False),
        "faulty": _params(flow_mode=1, flow_arrival_rate=0.05, **HARSH),
        "incast": _params(flow_mode=1, flow_arrival_rate=0.3,
                          incast_degree=8, flow_table_cap=8),
    }
    batch = S.make_batch([(p, 4 + i) for i, p in enumerate(rows.values())])
    res, state = S.run_sweep(batch, TICKS, chunk_ticks=CHUNK,
                             return_state=True)
    caps = {k: p.flow_table_cap for k, p in rows.items()}
    return dict(zip(rows, res)), state, caps


def _in_table(state, row, cap):
    rem = np.asarray(state.ft_rem)[row]
    return float(np.sum((rem > 0)
                        & (np.arange(rem.shape[1])[None, :] < cap)))


def test_flow_conservation_exact(flow_results):
    """started == completed + evicted + still-in-table, EXACTLY, in
    every mode — gating churn, harsh faults, and forced eviction
    included (counts are integral, the census must close)."""
    res, state, caps = flow_results
    for i, (mode, r) in enumerate(res.items()):
        resid = r["flows_started"] - (r["flows_completed"]
                                      + r["flows_evicted"]
                                      + _in_table(state, i, caps[mode]))
        assert resid == 0.0, (mode, resid)
        assert r["flows_started"] > 0, mode


def test_flow_eviction_accounting(flow_results):
    """8-way incast into an 8-slot table must evict; light rows must
    not (the table never fills at 0.05 arrivals/tick)."""
    res, _, _ = flow_results
    assert res["incast"]["flows_evicted"] > 0
    assert res["incast"]["flow_evicted_frac"] > 0.5
    for mode in ("web", "dm_base"):
        assert res[mode]["flows_evicted"] == 0.0, mode


def test_flow_fct_metrics_sane(flow_results):
    """Completions happen, slowdowns are >= 1 (FCT >= ideal FCT by
    construction), and per-class completion counts sum to the total."""
    res, _, _ = flow_results
    for mode, r in res.items():
        assert r["flows_completed"] > 0, mode
        for k in ("fct_slowdown_p50", "fct_slowdown_p99",
                  "fct_slowdown_mean"):
            assert r[k] >= 1.0, (mode, k, r[k])
        assert r["fct_p99_us"] >= r["fct_p50_us"], mode
        per_class = sum(r[f"flows_completed_{c}"]
                        for c in workloads.FLOW_CLASS_NAMES)
        assert per_class == r["flows_completed"], mode


def test_flow_wake_stalls_attributed(flow_results):
    """Under LC/DC the wake-stall delay attribution rides into the
    sampled path delay FCT uses — the gated flow rows must show it,
    and the harsh-fault row must actually exercise the fault model
    (its stalls flow through the same ``gating.stall_attribution``
    seam; the rare all-uplinks-dead fallback event itself is not
    guaranteed inside a 1000-tick light-load run)."""
    res, _, _ = flow_results
    assert res["web"]["delay_wake_stall_us"] > 0.0
    assert res["faulty"]["wake_retries"] > 0
    assert res["faulty"]["link_fault_frac"] > 0.0


def test_flow_validate_mode_clean():
    """The in-program validate guard (finite + packet conservation +
    flow census) passes on a flow batch."""
    batch = S.make_batch([
        (_params(flow_mode=1, flow_arrival_rate=0.1), 1),
        (_params(flow_mode=1, flow_arrival_rate=0.3, incast_degree=8,
                 flow_table_cap=8), 2)])
    S.run_sweep(batch, 500, chunk_ticks=250, validate=True)


def test_flow_batch_one_trace_one_transfer():
    """A flow grid is still ONE compile and ONE device->host fetch
    (flow knobs are Scenario leaves — no new compile sites)."""
    batch = S.make_batch([
        (_params(flow_mode=1, flow_arrival_rate=0.05), 1),
        (_params(flow_mode=1, flow_size_dist="datamining",
                 flow_arrival_rate=0.2, incast_degree=4), 2),
        (_params(), 3)])
    # unique chunk length => a fresh trace even after the other tests
    t0, h0 = S.TRACE_COUNT, S.HOST_TRANSFER_COUNT
    S.run_sweep(batch, 422, chunk_ticks=211)
    assert S.TRACE_COUNT - t0 == 1
    assert S.HOST_TRANSFER_COUNT - h0 == 1


# ---- the sampler property -----------------------------------------------

@settings(max_examples=60, deadline=None)
@given(st.integers(0, len(workloads.FLOW_DIST_NAMES) - 1),
       st.floats(0.0, 0.999999),
       st.floats(0.0, 0.999999))
def test_flow_size_sampler_monotone_integral(dist, u1, u2):
    """Inverse-CDF sampling is monotone in the uniform (within and
    across anchor segments) and yields integral sizes >= 1."""
    lo, hi = sorted((u1, u2))
    s = np.asarray(workloads.sample_flow_size_pkts(
        jnp.asarray([lo, hi], jnp.float32), dist))
    assert s[0] <= s[1]
    assert (s >= 1.0).all()
    assert (s == np.floor(s)).all()
    assert s[1] <= workloads.CDF_SIZE_PKTS[dist].max()


def _inverse_cdf_reference(u, dist):
    """The sampler's arithmetic in plain NumPy f32 over the same anchor
    tables: the segment by ``np.searchsorted``, the anchors by indexing.
    The power alone is ``jnp.power``: NumPy's float32 power and XLA's
    differ in the last bit, and ceil carries that into the size."""
    size, prob = workloads.CDF_SIZE_PKTS[dist], workloads.CDF_PROB[dist]
    seg = np.clip(np.searchsorted(prob, u, side="right") - 1,
                  0, len(prob) - 2)
    lo_s, hi_s = size[seg], size[seg + 1]
    lo_p, hi_p = prob[seg], prob[seg + 1]
    frac = np.clip((u - lo_p) / np.maximum(hi_p - lo_p, np.float32(1e-9)),
                   np.float32(0.0), np.float32(1.0))
    grow = np.asarray(jnp.power(hi_s / lo_s, frac))
    out = np.maximum(np.ceil(lo_s * grow), np.float32(1.0))
    assert out.dtype == np.float32
    return out


@pytest.mark.parametrize("jit", (False, True), ids=("eager", "jit"))
def test_flow_size_sampler_is_the_inverse_cdf_bit_for_bit(jit):
    """Every anchor probability, each anchor +- 1 ulp and 10^5 seeded
    uniforms per distribution, vmapped over a ``dist`` vector as the
    simulator runs it: the same bits as the NumPy inverse CDF."""
    rng = np.random.default_rng(20211202)
    ndist = len(workloads.FLOW_DIST_NAMES)
    rows = []
    for d in range(ndist):
        p = workloads.CDF_PROB[d]
        rows.append(np.concatenate([
            p, np.nextafter(p, np.float32(-1.0)),
            np.nextafter(p, np.float32(2.0)),
            rng.random(100_000, dtype=np.float32)]))
    u = np.stack(rows)
    sample = jax.vmap(workloads.sample_flow_size_pkts)
    if jit:
        sample = jax.jit(sample)
    got = np.asarray(sample(jnp.asarray(u), jnp.arange(ndist)))
    for d in range(ndist):
        want = _inverse_cdf_reference(u[d], d)
        np.testing.assert_array_equal(got[d].view(np.uint32),
                                      want.view(np.uint32))


def test_flow_size_classes():
    lo, hi = workloads.FLOW_CLASS_EDGES_PKTS
    got = np.asarray(workloads.flow_size_class(
        jnp.asarray([1, lo, lo + 1, hi, hi + 1], jnp.float32)))
    np.testing.assert_array_equal(got, [0, 0, 1, 1, 2])


# ---- knob plumbing ------------------------------------------------------

def test_flow_fingerprint_tracks_knobs():
    assert tuple(S.flow_fingerprint()) == S.FLOW_KNOBS
    assert S.flow_fingerprint(_params()) == S.flow_fingerprint()
    assert S.flow_fingerprint(_params(flow_mode=1)) != S.flow_fingerprint()


@pytest.mark.parametrize("kw,match", [
    (dict(flow_mode=2), "flow_mode"),
    (dict(flow_arrival_rate=-0.1), "flow_arrival_rate"),
    (dict(flow_arrival_rate=1.5), "flow_arrival_rate"),
    (dict(flow_size_dist="cachefollower"), "flow_size_dist"),
    (dict(incast_degree=0), "incast_degree"),
    (dict(incast_degree=C.MAX_INCAST_DEGREE + 1), "incast_degree"),
    (dict(flow_table_cap=0), "flow_table_cap"),
    (dict(flow_table_cap=C.FLOW_TABLE_SLOTS + 1), "flow_table_cap"),
])
def test_simparams_rejects_bad_flow_knobs(kw, match):
    with pytest.raises(ValueError, match=match):
        _params(**kw)
