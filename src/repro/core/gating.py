"""LC/DC stage controller: watermark-driven link activation/deactivation.

Pure, vectorized over a leading switch axis so the same controller runs
the RSW tier (128 switches x 4 uplinks) and the CSW tier (16 x 4), and
the beyond-paper ICI study (chips x links).

Semantics (Sec III-A):
  * stage k active -> uplinks [0, k) usable; stage >= 1 always (full
    connectivity invariant - this is what hides the laser turn-on).
  * any active queue backlog > hi watermark -> raise stage-up trigger:
    after STAGE_UP_DELAY ticks (control msg + ack + laser on + CDR) the
    next link becomes usable.
  * all active backlogs < lo watermark -> stage-down: the top link stops
    accepting traffic (drain), and once its queue is empty it powers off
    after STAGE_OFF_DELAY ticks, during which it is still charged at
    full power (conservative, Sec VI-B).

Optical fault model (opt-in, beyond-paper robustness axis)
----------------------------------------------------------
Real optical components are not the paper's perfect plane. ``gate_step``
grows an optional fault mode (engaged by passing ``link_ok``) with three
effects, each selected away bit-exactly when its knob is zero:

  * wake-time jitter: the turn-on delay becomes a per-event draw
    ``round(up_delay * (1 + jitter * (2u - 1)))`` (clamped >= 1) around
    the nominal instead of a constant;
  * transient wake failures: when the up-timer fires, the stage-up
    FAILS with probability ``wake_fail_prob`` and re-arms after a
    bounded ``WAKE_RETRY_BACKOFF_TICKS`` backoff plus a fresh turn-on
    delay (a flapping laser cannot hot-loop the controller);
  * min-connectivity fallback: hard transceiver faults (``FaultState``,
    evolved by ``fault_arrivals``) can leave a switch with zero usable
    healthy links. When that happens and a healthy real link exists,
    the policy force-wakes the CHEAPEST powered-off link (the lowest
    healthy index — raising the stage past it) the same tick, cancels
    any drain/off transition, and charges a fresh turn-on delay to the
    ``fault_stall`` attribution bin (``FaultState.wake``). Capacity is
    restored immediately in the fluid datapath; the stall is the
    latency price tag, exactly like the hi-watermark wake-stall split.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import constants as C


class GateState(NamedTuple):
    stage: jnp.ndarray        # (S,) int32 in [1, n_links]
    up_timer: jnp.ndarray     # (S,) int32, >0 while a link is turning on
    draining: jnp.ndarray     # (S,) bool, top stage is draining
    off_timer: jnp.ndarray    # (S,) int32, >0 while top link powers off
    hold: jnp.ndarray         # (S,) int32 anti-flap dwell after activation
    # links charged as ON: active + turning-on + draining + turning-off
    powered: jnp.ndarray      # (S, L) bool


class FaultState(NamedTuple):
    """Per-link hard-fault carry + the fault-forced wake stall.

    Lives alongside :class:`GateState` in the simulator's scan carry
    (kept separate so the controller state's contract is untouched and
    the fault axis stays optional for direct ``gate_step`` callers).
    """
    timer: jnp.ndarray    # (S, L) int32, > 0 while a transceiver is dead
    #                       (counts down the repair delay; 0 == healthy)
    wake: jnp.ndarray     # (S,) int32 remaining fault-forced wake stall
    #                       (feeds the fault_stall attribution bin)


def fault_init(n_switches: int, n_links: int) -> FaultState:
    return FaultState(jnp.zeros((n_switches, n_links), jnp.int32),
                      jnp.zeros((n_switches,), jnp.int32))


def fault_arrivals(timer: jnp.ndarray, u: jnp.ndarray,
                   powered: jnp.ndarray, link_real: jnp.ndarray,
                   fault_prob, repair_ticks,
                   plane_u: jnp.ndarray | None = None,
                   plane_fail_prob=0.0):
    """One tick of hard transceiver faults: Bernoulli arrivals on
    powered, healthy, REAL links (a dark or padded transceiver cannot
    die), then the repair countdown.

    timer: (S, L) int32 fault carry; u: (S, L) per-link uniforms;
    powered/link_real: (S, L) bool; fault_prob/repair_ticks: traced
    scalars (per-tick hazard = 1/MTBF, repair delay in ticks). Returns
    (new_timer, new_fault) with new_fault the (S, L) bool arrival mask
    (the simulator drops the dying link's queued packets into the
    fault-drop bin on it). ``fault_prob == 0`` leaves an all-zero timer
    all-zero — bit-inert.

    ``plane_u``/``plane_fail_prob`` model CORRELATED failure domains: a
    shared component (e.g. the laser comb feeding one optical plane)
    dying takes every link it feeds down in the same tick. ``plane_u``
    is an (S, L) uniform field in which all links of one plane carry
    the SAME draw (the caller broadcasts one draw per physical domain),
    so ``plane_u < plane_fail_prob`` strikes whole columns at once; the
    hit still only lands on powered, healthy, real links, and repairs
    share the per-link countdown. With ``plane_fail_prob == 0`` the OR
    adds an all-False mask (uniforms are >= 0, strict ``<``), so the
    default is structurally bit-inert — no epsilon, no new per-link
    stream consumed.
    """
    healthy = timer == 0
    hazard = u < fault_prob
    if plane_u is not None:
        hazard = hazard | (plane_u < plane_fail_prob)
    new_fault = healthy & powered & link_real & hazard
    timer = jnp.where(new_fault, jnp.asarray(repair_ticks, jnp.int32),
                      jnp.maximum(timer - 1, 0))
    return timer.astype(jnp.int32), new_fault


def fault_stall_ticks(fault: FaultState) -> jnp.ndarray:
    """(S,) float32: remaining ticks of a fault-forced link wake — the
    ``fault_stall`` delay-attribution analogue of ``wake_stall_ticks``.
    Exactly zero when no fallback wake is in flight (and with gating
    disabled, where the fallback never engages)."""
    return fault.wake.astype(jnp.float32)


def gate_init(n_switches: int, n_links: int) -> GateState:
    stage = jnp.ones((n_switches,), jnp.int32)
    powered = jnp.zeros((n_switches, n_links), bool).at[:, 0].set(True)
    z = jnp.zeros((n_switches,), jnp.int32)
    return GateState(stage, z, jnp.zeros((n_switches,), bool), z, z,
                     powered)


def usable_links(stage: jnp.ndarray, draining: jnp.ndarray,
                 n_links: int) -> jnp.ndarray:
    """(S, L) bool: links a scheduler may enqueue to this tick.

    The single definition of "usable" shared by the gate controller, the
    pure-jnp switch-tick oracle (kernels/ref.py) and the Pallas switch
    kernel: links [0, stage) minus a draining top link (which still
    serves its backlog but accepts no new packets; stage 1 never drains).
    """
    idx = jnp.arange(n_links)[None, :]
    usable = idx < stage[:, None]
    top = idx == (stage[:, None] - 1)
    usable &= ~(draining[:, None] & top & (stage[:, None] > 1))
    return usable


def active_mask(state: GateState, n_links: int) -> jnp.ndarray:
    """(S, L) bool: links the scheduler may use this tick."""
    return usable_links(state.stage, state.draining, n_links)


def wake_stall_ticks(state: GateState) -> jnp.ndarray:
    """(S,) float32: remaining ticks of an in-flight stage-up.

    The wake stall a packet arriving NOW inherits from the pending
    ``STAGE_UP_DELAY`` transition (control msg + ack + laser turn-on +
    CDR lock): positive only while a link is rising, i.e. the extra
    capacity the hi watermark already asked for is not live yet. The
    single definition used by the simulator's delay-attribution
    accumulators; with gating disabled ``up_timer`` never leaves 0, so
    the attribution is exactly zero.
    """
    return state.up_timer.astype(jnp.float32)


def stall_attribution(gate: GateState, fault: FaultState, gating_on):
    """(wake_stall, fault_stall) per switch, (S,) float32 each, masked
    to exactly 0.0 when ``gating_on`` is False.

    THE single stall-attribution pair: the simulator feeds it into both
    the packet-delay histogram and the flow engine's FCT samples, so
    wake/fault stalls attribute into flow completion times by
    construction — there is no second attribution path to drift. The
    mask belt-and-suspenders the structural invariants (``up_timer``
    never leaves 0 without gating, the fallback never engages), keeping
    the always-on attribution exactly zero.
    """
    wake = jnp.where(gating_on, wake_stall_ticks(gate), 0.0)
    fstall = jnp.where(gating_on, fault_stall_ticks(fault), 0.0)
    return wake, fstall


def watermark_triggers(queues: jnp.ndarray, stage: jnp.ndarray,
                       *, cap: float, hi: float, lo: float,
                       link_valid=None):
    """Shared hi/lo backlog-monitor definition (Sec III-B).

    queues: (S, L) per-port monitored backlogs. Returns (hi_trig, lo_trig)
    bool (S,). Used by gate_step and by the switch-tick kernels so the
    watermark semantics cannot drift between the controller and the
    datapath. cap/hi/lo may each be scalar or per-switch (S,).
    ``link_valid`` (optional (S, L) bool) restricts the monitor to the
    valid/healthy ports — a dead (hard-faulted) transceiver's backlog
    neither raises the hi trigger nor blocks the lo one.
    """
    def per_switch(v):
        v = jnp.asarray(v)
        return v[:, None] if v.ndim == 1 else v   # broadcast over ports
    cap, hi, lo = per_switch(cap), per_switch(hi), per_switch(lo)
    idx = jnp.arange(queues.shape[1])[None, :]
    act = idx < stage[:, None]
    if link_valid is not None:
        act = act & link_valid
    hi_t = jnp.any((queues > hi * cap) & act, axis=1)
    lo_t = jnp.all(jnp.where(act, queues < lo * cap, True), axis=1)
    return hi_t, lo_t


def gate_step(state: GateState, queues: jnp.ndarray,
              *, cap: float = C.QUEUE_CAP_PKTS,
              hi: float = C.HI_WATERMARK, lo: float = C.LO_WATERMARK,
              up_delay: int = C.STAGE_UP_DELAY_TICKS,
              off_delay: int = C.STAGE_OFF_DELAY_TICKS,
              dwell: int = C.STAGE_DWELL_TICKS,
              max_stage=None,
              link_ok=None, link_real=None, u_jitter=None, u_fail=None,
              wake_fail_prob=0.0, wake_jitter_frac=0.0,
              fault_wake=None, fallback=True,
              backoff: int = C.WAKE_RETRY_BACKOFF_TICKS):
    """One controller tick. queues: (S, L) backlogs in packets.

    ``max_stage`` caps the stage per switch (scalar or (S,) int); it
    defaults to L. The padded multi-site sweep engine passes each
    switch's REAL link count so a site whose link axis is padded to a
    wider hull never activates links it does not physically have.

    Fault mode (see module docstring) engages when ``link_ok`` — the
    (S, L) healthy-transceiver mask — is passed; it then returns
    ``(GateState, fault_wake', diag)`` instead of a bare GateState:

    ``link_real``     (S, L) bool, links that physically exist (defaults
                      to all); a switch whose REAL links are all faulted
                      is genuine connectivity loss — the fallback only
                      engages while a healthy real link remains.
    ``u_jitter``      (S,) uniforms driving the per-event turn-on delay
                      draw (``wake_jitter_frac`` around nominal).
    ``u_fail``        (S,) uniforms driving the transient wake failure
                      (``wake_fail_prob`` per firing; retry after
                      ``backoff`` + a fresh turn-on delay).
    ``fault_wake``    (S,) int32 carry of the fault-forced wake stall
                      (``FaultState.wake``); counted down here, re-armed
                      on a fallback force-wake.
    ``fallback``      bool (traced ok): enable the min-connectivity
                      force-wake.
    ``diag``          dict of (S,) bools: ``retries`` (a wake attempt
                      failed this tick), ``forced`` (the fallback fired).

    With ``wake_fail_prob == wake_jitter_frac == 0`` and ``link_ok``
    all-True the returned GateState is bit-identical to the legacy
    (fault-free) path — the zero-rate parity contract the simulator's
    one-program design relies on.
    """
    S, L = queues.shape
    idx = jnp.arange(L)[None, :]
    max_stage = jnp.asarray(L if max_stage is None else max_stage,
                            jnp.int32)
    fault_mode = link_ok is not None

    hi_trig, lo_trig = watermark_triggers(queues, state.stage,
                                          cap=cap, hi=hi, lo=lo)

    stage, up_timer, draining, off_timer, hold = (
        state.stage, state.up_timer, state.draining, state.off_timer,
        state.hold)
    hold = jnp.maximum(hold - 1, 0)

    if fault_mode:
        # per-event turn-on delay draw around nominal; jitter 0 -> the
        # round() is exactly the nominal (zero-rate bit-parity)
        up_f = jnp.asarray(up_delay, jnp.float32)
        eff_delay = jnp.maximum(jnp.round(
            up_f * (1.0 + wake_jitter_frac * (2.0 * u_jitter - 1.0))),
            1.0).astype(jnp.int32)                               # (S,)
    else:
        eff_delay = up_delay

    # --- stage-up: start turn-on unless at max / rising / powering off
    can_up = hi_trig & (stage < max_stage) & (up_timer == 0) \
        & (off_timer == 0)
    up_timer = jnp.where(can_up, eff_delay, up_timer)
    # cancel a drain if load returned
    draining = jnp.where(hi_trig, False, draining)
    # countdown; on expiry the new link becomes usable
    fired = up_timer == 1
    if fault_mode:
        # transient wake failure: the firing attempt fails and re-arms
        # after a bounded backoff plus a fresh turn-on delay
        failed = fired & (u_fail < wake_fail_prob)
        fired = fired & ~failed
    stage = jnp.where(fired, jnp.minimum(stage + 1, max_stage), stage)
    hold = jnp.where(fired, dwell, hold)     # anti-flap dwell
    up_timer = jnp.maximum(up_timer - 1, 0)
    if fault_mode:
        up_timer = jnp.where(failed, backoff + eff_delay, up_timer)

    # --- stage-down: mark the top link draining (never stage 1)
    start_drain = lo_trig & (stage > 1) & ~draining & (up_timer == 0) \
        & (off_timer == 0) & (hold == 0)
    draining = draining | start_drain

    # drained? (top queue empty) -> drop the stage NOW (link unusable) and
    # begin the 10us power-off transition (still charged: off_timer)
    # a one-hot select over the link axis, not a gather (a TPU pays per
    # gathered element); exactly one term is non-zero
    top_q = jnp.sum(jnp.where(idx == (stage - 1)[:, None], queues, 0.0),
                    axis=1)
    begin_off = draining & (top_q <= 0) & (stage > 1)
    stage = jnp.where(begin_off, stage - 1, stage)
    off_timer = jnp.where(begin_off, off_delay, off_timer)
    draining = jnp.where(begin_off, False, draining)
    off_timer = jnp.maximum(off_timer - 1, 0)

    diag = None
    if fault_mode:
        # --- min-connectivity fallback: a switch whose usable prefix is
        # all dead force-wakes the cheapest healthy link (lowest index)
        # the same tick, so the datapath never sees a repairable switch
        # with zero usable links; the turn-on delay is charged to the
        # fault_stall attribution carry instead of stalling the fluid
        ok = link_ok if link_real is None else (link_ok & link_real)
        usable_ok = usable_links(stage, draining, L) & ok
        has_ok = jnp.any(ok, axis=1)
        do_fb = ~jnp.any(usable_ok, axis=1) & has_ok & fallback
        first_ok = jnp.argmax(ok, axis=1).astype(jnp.int32)
        tgt = jnp.minimum(first_ok + 1, max_stage)
        stage = jnp.where(do_fb, jnp.maximum(stage, tgt), stage)
        draining = jnp.where(do_fb, False, draining)
        off_timer = jnp.where(do_fb, 0, off_timer)
        hold = jnp.where(do_fb, jnp.asarray(dwell, jnp.int32), hold)
        fwake = jnp.maximum(jnp.asarray(fault_wake) - 1, 0)
        fwake = jnp.where(do_fb, eff_delay, fwake).astype(jnp.int32)
        diag = {"retries": failed, "forced": do_fb}

    # --- power accounting: on, rising, draining or falling => powered
    powered = idx < stage[:, None]
    powered |= (up_timer > 0)[:, None] & (idx == stage[:, None])  # rising
    powered |= (off_timer > 0)[:, None] & (idx == stage[:, None])  # falling
    powered |= draining[:, None] & (idx == (stage[:, None] - 1))

    out = GateState(stage, up_timer, draining, off_timer, hold, powered)
    if fault_mode:
        return out, fwake, diag
    return out
