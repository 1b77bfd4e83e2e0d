"""The traffic generator: a flow load becomes the arrival rate that
offers it, with the mean flow size the engine's own sampler gives."""
import jax
import numpy as np
import pytest

from chipbench import run


@pytest.mark.parametrize("dist", ("websearch", "datamining"))
def test_mean_flow_size_is_the_samplers(dist):
    from repro.core import workloads
    cdf = run.Cell("fig2_flows").cfg["flows"]["size_cdfs"][dist]
    u = jax.random.uniform(jax.random.PRNGKey(0), (1 << 22,))
    sizes = np.asarray(workloads.sample_flow_size_pkts(
        u, workloads.FLOW_DIST_NAMES.index(dist)), np.float64)
    # the tail of the datamining sizes reaches 778,667 packets
    sem = sizes.std() / np.sqrt(len(sizes))
    assert abs(run.mean_flow_pkts(cdf) - sizes.mean()) < 4 * sem


def test_load_offers_its_share_of_the_access_links():
    cell = run.Cell("fig2_flows")
    fl, site = cell.cfg["flows"], cell.cfg["site"]
    for r in cell.rows:
        mean = run.mean_flow_pkts(fl["size_cdfs"][r["flow_size_dist"]])
        offered = r["flow_arrival_rate"] * mean
        assert offered == pytest.approx(
            r["load"] * site["servers_per_rack"]
            * fl["line_rate_pkts_per_tick"])
