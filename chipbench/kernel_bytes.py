"""Bytes the ``switch_step`` contract reads and writes per call.

Counted from the logical operands and results of one vmapped call over a
batch of ``B`` scenarios, for a tier of ``S`` switches with ``L`` ports
and ``K`` traffic components (kernels/ref.py states the contract):

  reads   queues (B,S,L,K) f32, stage (B,S) i32, arrivals (B,S,K) f32,
          draining (B,S) bool, valid (B,S,L) bool, cap/hi/lo (B,) f32
  writes  queues and served (B,S,L,K) f32, hi/lo triggers (B,S) i32,
          dropped, enq_wait, occ_m1, occ_m2 (B,S) f32

A padded or re-laid-out operand of one implementation does not count:
the number holds for any implementation of the contract. The kernel
does no matrix work, so these bytes over HBM bandwidth are its least
time.
"""
from __future__ import annotations

F32 = I32 = 4
BOOL = 1


def switch_step_bytes(B: int, S: int, L: int, K: int) -> int:
    reads = (B * S * L * K * F32 + B * S * I32 + B * S * K * F32
             + B * S * BOOL + B * S * L * BOOL + 3 * B * F32)
    writes = 2 * B * S * L * K * F32 + 2 * B * S * I32 + 4 * B * S * F32
    return reads + writes


def tier_shapes(site: dict) -> dict:
    """(S, L, K) of the two tiers of a Fig 2 site: the RSWs with one
    uplink per cluster CSW and an [intra, inter] split, and the CSWs
    with one 40G uplink per FC."""
    racks = site["n_clusters"] * site["racks_per_cluster"]
    csws = site["n_clusters"] * site["csw_per_cluster"]
    return {"rsw": (racks, site["csw_per_cluster"], 2),
            "csw": (csws, site["n_fc"], 1)}


def bytes_per_tick(site: dict, rows_per_device: int) -> int:
    """Bytes of one tick's two calls, one per tier, on one device."""
    return sum(switch_step_bytes(rows_per_device, *shape)
               for shape in tier_shapes(site).values())
