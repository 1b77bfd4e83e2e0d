"""The switch kernel's byte count against a count by hand at the tier
shapes of the Fig 2 site (FBSite(): 128 RSWs x 4 uplinks x [intra,
inter], 16 CSWs x 4 40G uplinks), 10 scenarios per call."""
import json
from pathlib import Path

from chipbench import kernel_bytes as kb

SITE = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "fig2_rate.json").read_text())["site"]


def test_rsw_call_by_hand():
    # reads: queues 10*128*4*2*4 + stage 10*128*4 + arrivals 10*128*2*4
    #        + draining 10*128 + valid 10*128*4 + cap/hi/lo 3*10*4
    reads = 40960 + 5120 + 10240 + 1280 + 5120 + 120
    # writes: queues + served 2*40960, hi/lo 2*5120, four (B,S) floats
    writes = 81920 + 10240 + 20480
    assert kb.switch_step_bytes(10, 128, 4, 2) == reads + writes == 175480


def test_csw_call_by_hand():
    reads = 2560 + 640 + 640 + 160 + 640 + 120
    writes = 5120 + 1280 + 2560
    assert kb.switch_step_bytes(10, 16, 4, 1) == reads + writes == 13720


def test_tier_shapes_of_the_fig2_site():
    assert kb.tier_shapes(SITE) == {"rsw": (128, 4, 2), "csw": (16, 4, 1)}
    assert kb.bytes_per_tick(SITE, 10) == 175480 + 13720
