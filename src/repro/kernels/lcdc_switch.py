"""LC/DC switch datapath step as a Pallas kernel.

The TPU-native analogue of the paper's FPGA pipeline (Sec III-B): for a
tile of switches, one tick of
  (1) min-backlog output-queue selection over the stage-enabled ports
      (the per-stage CAM lookup + weighted scheduler), honouring a
      draining top port that serves but no longer accepts traffic,
  (2) arrival enqueue with capacity clamp (drop counting) — arrivals are
      a per-switch vector of K traffic components (the simulator's
      [intra, inter] split), enqueued proportionally,
  (3) up-to-serve_rate pkt/port service over active ports, split
      proportionally across the K components,
  (4) high/low watermark trigger generation (the backlog monitor),
  (5) backlog-age / occupancy-moment taps: the pre-enqueue backlog the
      arriving packet queues behind (in ticks-to-serve) plus the first
      and second post-serve occupancy moments over the output ports —
      the oracle-checked feed of the simulator's in-scan packet-delay
      histograms.

All switches in a tile advance in one VPU-wide vector step. Every
operand is a 2-D (S, ·) array: the K queue components ride in as K
separate (S, L) planes and the arrival vector as K (S, 1) columns, so
the kernel body never reshapes and Mosaic sees only rank-2 layouts.
Masks are float32 0/1 values reduced with max ("all" is "no 1 in the
complement") and the min-backlog tie-break is a first-index pick (iota
+ min), because Mosaic lowers neither bool reductions nor cumsum.
cap/hi/lo ride in as per-switch operand columns rather than
compile-time constants so per-scenario values (the batched sweep
engine's array-valued knobs) trace through one compile.

The switch axis is padded up to the block size and outputs sliced back,
so odd-sized tiers (e.g. the 16-CSW tier under a 128 block) work.

The sim's pure-jnp path (ref.switch_step_ref) is the oracle and the CPU
execution path; on TPU ops.switch_step dispatches here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 1e30


def _kernel(*refs, n_comp: int, serve_rate: float):
    K = n_comp
    q_refs = refs[:K]                               # K x (bs, L)
    stage_ref, drain_ref, valid_ref = refs[K:K + 3]
    arr_refs = refs[K + 3:2 * K + 3]                # K x (bs, 1)
    cap_ref, hi_ref, lo_ref = refs[2 * K + 3:2 * K + 6]
    qo_refs = refs[2 * K + 6:3 * K + 6]
    srv_refs = refs[3 * K + 6:4 * K + 6]
    hi_o_ref, lo_o_ref, drop_ref, wait_ref, m1_ref, m2_ref = \
        refs[4 * K + 6:]

    qs = [r[...] for r in q_refs]
    stage = stage_ref[...]                          # (bs, 1) int32
    link_valid = valid_ref[...].astype(jnp.float32)  # (bs, L) 0/1
    arrs = [r[...] for r in arr_refs]
    cap = cap_ref[...]                              # (bs, 1)
    bs, L = link_valid.shape
    # a switch is live iff any of its ports is valid
    vswitch = jnp.max(link_valid, axis=1, keepdims=True) > 0.0  # (bs, 1)

    idx = jax.lax.broadcasted_iota(jnp.int32, (bs, L), 1)
    act = jnp.where(idx < stage, link_valid, 0.0)   # (bs, L) 0/1
    # a draining top port (stage > 1) serves but accepts nothing
    drain_top = jnp.where((drain_ref[...] != 0) & (stage > 1), 1.0, 0.0)
    usable = jnp.where(idx == stage - 1, act * (1.0 - drain_top), act)
    qtot = functools.reduce(jnp.add, qs)            # (bs, L)

    # (1) min-backlog selection among usable ports, ties to the lowest
    # index: the first lane holding the minimum
    masked = jnp.where(usable > 0.0, qtot, BIG)
    mn = jnp.min(masked, axis=1, keepdims=True)
    idx_f = idx.astype(jnp.float32)
    first = jnp.min(jnp.where(masked == mn, idx_f, float(L)), axis=1,
                    keepdims=True)
    pick = jnp.where(idx_f == first, 1.0, 0.0)      # (bs, L) one-hot
    # per-link faults can leave a live switch with no usable port: keep
    # the BIG sentinel out of the taps and collapse the room to 0 so
    # the whole arrival drops (matches ref.switch_step_ref)
    has_usable = jnp.max(usable, axis=1, keepdims=True) > 0.0
    mn0 = jnp.where(has_usable, mn, 0.0)

    # (5a) backlog-age of the pick: what an arrival queues behind
    wait_ref[...] = jnp.where(vswitch, mn0, 0.0) / serve_rate

    # (2) enqueue with capacity clamp, proportional over components; an
    # arrival at a switch with no valid port left (all transceivers
    # hard-faulted) is a counted drop, not a silent loss (padded
    # switches receive zero arrivals, so they still report 0)
    add_tot = functools.reduce(jnp.add, arrs)       # (bs, 1)
    room = jnp.where(has_usable, jnp.maximum(cap - mn0, 0.0), 0.0)
    scale = jnp.minimum(1.0, room / jnp.maximum(add_tot, 1e-9))
    drop_ref[...] = jnp.where(vswitch, add_tot * (1.0 - scale), add_tot)
    qs = [q + pick * (a * scale) for q, a in zip(qs, arrs)]

    # (3) serve up to serve_rate pkts per active port, proportional
    qtot = functools.reduce(jnp.add, qs)
    serve_tot = jnp.minimum(qtot, serve_rate) * act
    # clamped to 1: TPU division is not correctly rounded (see ref.py)
    frac = jnp.minimum(serve_tot / jnp.maximum(qtot, 1e-9), 1.0)
    for q, qo_ref, srv_ref in zip(qs, qo_refs, srv_refs):
        served = q * frac
        qo_ref[...] = q - served
        srv_ref[...] = served

    # (4) watermark triggers on post-serve backlogs; invalid switches
    # never trigger (lo would otherwise fire vacuously on act==empty)
    qpost = qtot - serve_tot

    # (5b) post-serve occupancy moments over the output ports
    m1_ref[...] = jnp.where(vswitch,
                            jnp.sum(qpost, axis=1, keepdims=True), 0.0)
    m2_ref[...] = jnp.where(vswitch,
                            jnp.sum(qpost * qpost, axis=1, keepdims=True),
                            0.0)
    over_hi = jnp.where(qpost > hi_ref[...] * cap, act, 0.0)
    hi_o_ref[...] = jnp.where(jnp.max(over_hi, axis=1, keepdims=True) > 0.0,
                              1, 0).astype(jnp.int32)
    under_lo = jnp.where((act > 0.0) & ~(qpost < lo_ref[...] * cap),
                         1.0, 0.0)
    lo_o_ref[...] = jnp.where(
        (jnp.max(under_lo, axis=1, keepdims=True) == 0.0) & vswitch,
        1, 0).astype(jnp.int32)


def switch_step(queues, stage, arrivals, draining=None, *, valid=None,
                cap=20.0, hi=0.75, lo=0.22, serve_rate=1.0, block_s=128,
                interpret=False):
    """queues (S, L, K) or (S, L); stage (S,) int32; arrivals (S, K) or
    (S,); draining (S,) bool; valid (S,) bool padding mask (invalid
    switches are inert) or (S, L) bool per-link usability mask (the
    fault-injection axis: dead ports on live switches). Same contract
    as ref.switch_step_ref: returns (new_queues, served, hi_trig,
    lo_trig, dropped, enq_wait, occ_m1, occ_m2). ``interpret=True``
    runs the body in the Pallas interpreter (any backend)."""
    squeeze = queues.ndim == 2
    if squeeze:
        queues = queues[..., None]
        arrivals = arrivals[..., None]
    S, L, K = queues.shape
    if draining is None:
        draining = jnp.zeros((S,), bool)
    if valid is None:
        valid = jnp.ones((S,), bool)
    # per-switch masks broadcast to the kernel's per-link operand
    link_valid = jnp.broadcast_to(valid[:, None], (S, L)) \
        if valid.ndim == 1 else jnp.asarray(valid, bool)

    # pad the switch axis to the block size (idle switches: stage 1,
    # empty queues, zero arrivals, valid=0) and slice the outputs back
    bs = min(block_s, _round_up(S, 8))
    Sp = _round_up(S, bs)
    pad = Sp - S
    f32 = queues.dtype
    qp = jnp.pad(queues, ((0, pad), (0, 0), (0, 0)))
    stage_p = jnp.pad(stage, (0, pad), constant_values=1)[:, None]
    drain_p = jnp.pad(draining, (0, pad)).astype(jnp.int32)[:, None]
    valid_p = jnp.pad(link_valid, ((0, pad), (0, 0))).astype(jnp.int32)
    arr_p = jnp.pad(arrivals, ((0, pad), (0, 0)))
    def col(v):
        # scalar or per-switch (S,) knob -> padded (Sp, 1) operand column
        v = jnp.asarray(v, f32)
        if v.ndim == 0:
            return jnp.full((Sp, 1), v)
        return jnp.pad(v.reshape(-1), (0, pad))[:, None]

    kern = functools.partial(_kernel, n_comp=K, serve_rate=float(serve_rate))
    spec_1 = pl.BlockSpec((bs, 1), lambda i: (i, 0))
    spec_l = pl.BlockSpec((bs, L), lambda i: (i, 0))
    plane = jax.ShapeDtypeStruct((Sp, L), f32)
    column = jax.ShapeDtypeStruct((Sp, 1), f32)
    flag = jax.ShapeDtypeStruct((Sp, 1), jnp.int32)
    outs = pl.pallas_call(
        kern,
        grid=(Sp // bs,),
        in_specs=[spec_l] * K + [spec_1, spec_1, spec_l] + [spec_1] * K
        + [spec_1] * 3,
        out_specs=[spec_l] * (2 * K) + [spec_1] * 6,
        out_shape=[plane] * (2 * K) + [flag, flag] + [column] * 4,
        interpret=interpret,
        name="lcdc_switch_step",
    )(*[qp[..., k] for k in range(K)], stage_p, drain_p, valid_p,
      *[arr_p[:, k:k + 1] for k in range(K)], col(cap), col(hi), col(lo))
    qo = jnp.stack(outs[:K], axis=-1)[:S]
    srv = jnp.stack(outs[K:2 * K], axis=-1)[:S]
    hi_t, lo_t, drop, wait, m1, m2 = outs[2 * K:]
    if squeeze:
        qo, srv = qo[..., 0], srv[..., 0]
    return (qo, srv, hi_t[:S, 0], lo_t[:S, 0], drop[:S, 0], wait[:S, 0],
            m1[:S, 0], m2[:S, 0])


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m
