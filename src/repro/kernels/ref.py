"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

* attention_ref     - reuses the model's chunked online-softmax attention
                      (repro/models/attention.py), itself validated against
                      a naive softmax in the tests.
* attention_naive   - O(T*S) direct softmax (small shapes only).
* wkv_ref           - sequential RWKV-6 recurrence (repro/models/rwkv6.py).
* switch_step_ref   - one LC/DC switch tick, identical semantics to
                      kernels/lcdc_switch.py. This is THE shared
                      semantic definition of the per-switch datapath:
                      the simulator hot loop routes through it (via
                      ops.switch_step) on CPU, and the Pallas kernel is
                      validated against it, so min-backlog pick /
                      capacity clamp / serve / watermark logic lives in
                      exactly one jnp implementation (usable-link and
                      watermark predicates are imported from
                      core/gating.py, the controller's own definitions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import gating
from repro.models.attention import chunked_attention as attention_ref  # noqa
from repro.models.rwkv6 import wkv_scan as wkv_ref  # noqa

BIG = 1e30


def attention_naive(q, k, v, *, causal=True, swa_window=0):
    B, T, H, dq = q.shape
    S = k.shape[1]
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dq ** -0.5
    qp = jnp.arange(T)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= qp >= kp
    if swa_window:
        mask &= (qp - kp) < swa_window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return out.astype(v.dtype)


def switch_step_ref(queues, stage, arrivals, draining=None, *,
                    valid=None, cap=20.0, hi=0.75, lo=0.22,
                    serve_rate=1.0):
    """One switch tick for a tier of S switches with L output ports.

    queues:   (S, L, K) per-port backlogs split into K traffic
              components (e.g. K=2 for the RSW's [intra, inter] split),
              or (S, L) for the K=1 shorthand.
    stage:    (S,) int32 active-stage counts (ports [0, stage) enabled).
    arrivals: (S, K) — or (S,) with 2-D queues — per-switch arrival
              vector enqueued onto the min-backlog usable port.
    draining: (S,) bool; a draining top port serves but does not accept.
    valid:    (S,) bool padding mask for heterogeneous-site batches, or
              (S, L) bool per-LINK usability mask (the fault-injection
              axis: a hard-faulted transceiver is a dead port on an
              otherwise live switch — it accepts nothing and serves
              nothing, while the switch's healthy ports keep working).
              A switch with no valid port at all is inert: it accepts
              nothing, serves nothing, raises no triggers, and its
              queues pass through unchanged — but any arrival fed to it
              IS counted as a drop (a whole-switch fault outage must
              not silently lose packets). Padded switches stay
              drop-free because callers feed them zero arrivals;
              arrivals at a live switch whose usable ports are all
              dead are counted as drops too.

    Semantics per switch: (1) pick the usable port with the least total
    backlog, (2) enqueue the arrival vector there, proportionally scaled
    so the port total never exceeds ``cap`` (the clipped excess is
    dropped), (3) serve up to ``serve_rate`` pkts/tick per active port,
    split proportionally across the K components, (4) raise hi/lo
    watermark triggers on the post-serve backlogs, (5) emit the
    backlog-age / occupancy moments that feed the simulator's in-scan
    delay histograms.

    Returns (new_queues, served, hi_trig, lo_trig, dropped, enq_wait,
    occ_m1, occ_m2) where served has the queues' shape, hi/lo are int32
    (S,), dropped is (S,), and the moment outputs are (S,) float:

    enq_wait: the queue wait a packet arriving THIS tick inherits — the
              pre-enqueue backlog of the min-backlog pick divided by
              ``serve_rate`` (ticks until head-of-line). 0 for invalid
              switches.
    occ_m1:   sum over the switch's output ports of the post-serve
              per-port backlog (first occupancy moment).
    occ_m2:   sum of the squared post-serve per-port backlogs (second
              moment; m2/n - (m1/n)^2 is the backlog variance over
              port-ticks). Both 0 for invalid switches.
    """
    squeeze = queues.ndim == 2
    if squeeze:
        queues = queues[..., None]
        arrivals = arrivals[..., None]
    S, L, K = queues.shape
    if draining is None:
        draining = jnp.zeros((S,), bool)
    if valid is None:
        valid = jnp.ones((S,), bool)
    # (S,) per-switch padding mask or (S, L) per-link fault/usability
    # mask — broadcast to per-link; a switch is live iff any port is
    link_valid = valid[:, None] if valid.ndim == 1 \
        else jnp.asarray(valid, bool)
    vswitch = jnp.any(link_valid, axis=1)               # (S,)

    act = (jnp.arange(L)[None, :] < stage[:, None]) & link_valid
    usable = gating.usable_links(stage, draining, L) & link_valid
    qtot = jnp.sum(queues, axis=2)                      # (S, L)

    # (1) min-backlog usable port, ties to the lowest index
    masked = jnp.where(usable, qtot, BIG)
    mn = jnp.min(masked, axis=1, keepdims=True)
    pick = masked == mn
    pick &= jnp.cumsum(pick.astype(jnp.int32), axis=1) == 1
    # per-link faults can leave a live switch with NO usable port this
    # tick (its pick row is all-False): guard the BIG sentinel out of
    # the taps and drop the whole arrival below (room collapses to 0)
    has_usable = jnp.any(usable, axis=1)
    mn0 = jnp.where(has_usable, mn[:, 0], 0.0)

    # (5a) backlog-age of the pick: what an arrival queues behind
    enq_wait = jnp.where(vswitch, mn0, 0.0) / serve_rate

    # (2) enqueue with capacity clamp (proportional over components);
    # an arrival at a switch with NO valid port left (every transceiver
    # hard-faulted) is a counted drop, not a silent loss — packet
    # conservation must survive whole-switch fault outages. Padded
    # (invalid) switches still report 0: they receive zero arrivals.
    add_tot = jnp.sum(arrivals, axis=1)                 # (S,)
    room = jnp.where(has_usable,
                     jnp.maximum(cap - mn0, 0.0), 0.0)
    scale = jnp.minimum(1.0, room / jnp.maximum(add_tot, 1e-9))
    dropped = jnp.where(vswitch, add_tot * (1.0 - scale), add_tot)
    q = queues + pick.astype(queues.dtype)[..., None] \
        * (arrivals * scale[:, None])[:, None, :]

    # (3) serve up to serve_rate pkts per active port, proportional
    # across components (a draining top port keeps draining: it is
    # active until the drain completes and the stage drops)
    qtot = jnp.sum(q, axis=2)
    serve_tot = jnp.minimum(qtot, serve_rate) * act
    # serve_tot <= qtot, so frac <= 1 under IEEE division; a TPU divides
    # through an approximate reciprocal (x / x can exceed 1 by an ulp),
    # which would serve more than the queue holds and leave a negative
    # backlog that the 1e-9 floor then amplifies without bound. The
    # clamp is a no-op where division is exact (bit-identical on CPU).
    frac = jnp.minimum(serve_tot / jnp.maximum(qtot, 1e-9), 1.0)
    served = q * frac[..., None]
    q = q - served

    # (5b) post-serve occupancy moments over the switch's output ports
    qpost = qtot - serve_tot
    occ_m1 = jnp.where(vswitch, jnp.sum(qpost, axis=1), 0.0)
    occ_m2 = jnp.where(vswitch, jnp.sum(qpost * qpost, axis=1), 0.0)

    # (4) watermark triggers on post-serve backlogs (shared definition,
    # restricted to the valid/healthy ports); invalid switches never
    # trigger
    hi_t, lo_t = gating.watermark_triggers(qpost, stage, cap=cap, hi=hi,
                                           lo=lo, link_valid=link_valid)
    hi_t, lo_t = hi_t & vswitch, lo_t & vswitch
    if squeeze:
        q, served = q[..., 0], served[..., 0]
    return (q, served, hi_t.astype(jnp.int32), lo_t.astype(jnp.int32),
            dropped, enq_wait, occ_m1, occ_m2)
