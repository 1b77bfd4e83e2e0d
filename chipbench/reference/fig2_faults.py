"""Plain reference of the Fig 2 LC/DC site with failing optics (one
scenario): the semantics the sweep engine states for the configuration
``fig2_faults``.

It is ``fig2.py``'s reference with the optical fault model added, and
takes from ``fig2.py`` (loaded by path, as the harness loads a
reference) everything the fault model does not reach into: the
scenario knobs, the initial state, the accumulators and histograms,
the run in blocks folded in float64, what the site holds at the end,
and the paper's metrics. What it writes anew is the tick, which faults
reach into throughout, and in it:

- hard faults: per tick a Bernoulli hazard ``1 / link_mtbf_ticks`` on
  every powered, healthy link, drawn from the fixed branch 0x7F000001
  (RSW tier) or 0x7F000002 (CSW tier) of the tick's uniform key, one
  block of ``2 + FAULT_DRAW_LINKS`` uniforms per switch keyed by its
  logical id (entry 0 the wake jitter, entry 1 the wake failure, entry
  2 + l the hazard of link l);
- plane outages: one more hazard ``plane_fail_prob`` per domain, shared
  by every link of the domain: on the RSW tier one draw per cluster and
  plane (branch 0x7F000005, the cluster's id, a block of
  ``FAULT_DRAW_LINKS``), on the CSW tier one per FC (branch 0x7F000006,
  one block for the site, the FC's entry);
- repair: a dead link counts ``repair_ticks`` down and is healthy at 0;
- the fault-drop bin: a link that dies this tick loses what its queue
  holds after this tick's serve (RSW uplink and CSW uplink queues);
- dead links in the datapath: a dead port neither accepts nor serves in
  either switch tier (a switch with no usable port drops its arrivals,
  counted as drops), an FC or CSW serves no packet down a dead link,
  and its stranded backlog rides the ring like that of a gated link;
- the controller: a per-event turn-on delay ``max(round(stage_up_delay
  x (1 + jitter x (2u - 1))), 1)``, a wake that fails with
  ``wake_fail_prob`` and re-arms after ``WAKE_RETRY_BACKOFF_TICKS`` plus
  a fresh delay, and the fallback: a switch whose usable links are all
  dead while a healthy one exists raises its stage to its lowest healthy
  link the same tick, cancels a drain or power-off, restarts the dwell,
  and charges the drawn turn-on delay to its fault stall;
- the fault stall in each packet's delay sample, as the wake stall is;
- the connectivity audit on the end-of-tick state: a switch with a
  healthy link but no usable healthy one counts a lost tick;
- power: a dead transceiver is dark, whatever the controller thinks.

Departures from the program: it runs one site at its own shape (no
padded hull, so every switch and link is real) and the rate edge only
(the configuration has no flow engine; a configuration with ``flows``
is refused). Gating off holds every link up and the controller still,
as in the program: faults strike there too, and the fallback, its
stall, retries and forced wakes count only under gating.

``dtype`` selects the precision of every float value, as in ``fig2.py``:
float32 is the configuration's own, bfloat16 the control. No matrix
product appears, so ``jax.default_matmul_precision("highest")`` changes
nothing; it is set around the run all the same, so that none that is
added later runs in a lower precision on a TPU.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _load_fig2():
    path = Path(__file__).with_name("fig2.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_fig2_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


fig2 = _load_fig2()

RSW_FAULT_BRANCH = 0x7F000001
CSW_FAULT_BRANCH = 0x7F000002
RSW_PLANE_BRANCH = 0x7F000005
CSW_PLANE_BRANCH = 0x7F000006
#: the engine's own constants, which the configuration does not set: the
#: backoff of a failed wake (repro.core.constants.WAKE_RETRY_BACKOFF_TICKS)
#: and the width of a switch's block of link hazard draws
#: (repro.core.simulator.MAX_FAULT_LINKS), which fixes the streams
WAKE_RETRY_BACKOFF_TICKS = 4
FAULT_DRAW_LINKS = 16

#: fault accumulators, beyond fig2's
FAULT_ACC_KEYS = ("fault_drops", "fault_link_ticks", "wake_retries",
                  "forced_wakes", "conn_loss", "delay_fault_sum")


def usable(stage, drain, n_links):
    """(S, L): links [0, stage) less a draining top link (stage > 1)."""
    idx = jnp.arange(n_links)[None, :]
    top = idx == stage[:, None] - 1
    return (idx < stage[:, None]) & ~(drain[:, None] & top
                                      & (stage[:, None] > 1))


class FaultRef(fig2.Ref):
    """The reference with the fault model, for one configuration at one
    precision."""

    def __init__(self, cfg: dict, dtype=jnp.float32):
        if cfg.get("flows"):
            raise ValueError("the fault reference models the rate edge "
                             "only; this configuration has a flow engine")
        super().__init__(cfg, dtype)
        op = cfg["optics"]
        mtbf = float(op["link_mtbf_ticks"])
        # the program takes its knobs in float32; a lower precision
        # rounds from there
        self.fault_prob = self.knob(1.0 / mtbf if mtbf > 0 else 0.0)
        self.plane_prob = self.knob(op["plane_fail_prob"])
        self.wake_fail = self.knob(op["wake_fail_prob"])
        self.jitter = self.knob(op["wake_jitter_frac"])
        self.repair = int(op["repair_ticks"])
        self.fallback = bool(op["fault_fallback"])

    def knob(self, x):
        return jnp.asarray(np.float32(x)).astype(self.dt)

    # ---- state --------------------------------------------------------------
    def init(self, knobs, seed):
        st = super().init(knobs, seed)
        z = jnp.zeros
        st.update(rsw_timer=z((self.R, self.P), jnp.int32),
                  rsw_fwake=z((self.R,), jnp.int32),
                  csw_timer=z((self.NC, self.CUP), jnp.int32),
                  csw_fwake=z((self.NC,), jnp.int32))
        return st

    def zero_acc(self):
        acc = super().zero_acc()
        acc.update({k: jnp.zeros((), self.dt) for k in FAULT_ACC_KEYS})
        return acc

    # ---- one switch tier, dead ports masked ---------------------------------
    def tier(self, q, stage, drain, arr, serve_rate, ok):
        """q (S, L, K), arr (S, K), ok (S, L) healthy ports. Returns (q,
        served, dropped, wait)."""
        cap = self.f(self.cfg["switch"]["queue_cap_pkts"])
        L = q.shape[1]
        idx = jnp.arange(L)[None, :]
        act = (idx < stage[:, None]) & ok
        use = usable(stage, drain, L) & ok
        has = jnp.any(use, axis=1)
        qtot = jnp.sum(q, axis=2)
        masked = jnp.where(use, qtot, self.f(fig2.BIG))
        port = jnp.argmin(masked, axis=1)                 # lowest on ties
        mn = jnp.where(has, jnp.min(masked, axis=1), 0.0)
        wait = mn / serve_rate
        add = jnp.sum(arr, axis=1)
        # no usable port: no room, the whole arrival is dropped
        room = jnp.where(has, jnp.maximum(cap - mn, 0.0), 0.0)
        scale = jnp.minimum(1.0, room / jnp.maximum(add, self.f(1e-9)))
        dropped = add * (1.0 - scale)
        onehot = ((idx == port[:, None]) & has[:, None]).astype(q.dtype)
        q = q + onehot[:, :, None] * (arr * scale[:, None])[:, None, :]
        qtot = jnp.sum(q, axis=2)
        serve = jnp.minimum(qtot, serve_rate) * act
        frac = jnp.minimum(serve / jnp.maximum(qtot, self.f(1e-9)), 1.0)
        served = q * frac[:, :, None]
        return q - served, served, dropped, wait

    # ---- hard faults: arrivals on powered healthy links, then repair --------
    def faults(self, timer, u, plane_u, powered):
        new = (timer == 0) & powered & ((u < self.fault_prob)
                                        | (plane_u < self.plane_prob))
        timer = jnp.where(new, self.repair, jnp.maximum(timer - 1, 0))
        return timer.astype(jnp.int32), new

    # ---- the watermark controller with failing wakes and the fallback -------
    def gate(self, g, queues, ok, u_jit, u_fail, fwake):
        """One controller tick. Returns (gate, fault stall carry, failed
        wakes (S,), forced wakes (S,))."""
        sw = self.cfg["switch"]
        cap = self.f(sw["queue_cap_pkts"])
        L = queues.shape[1]
        idx = jnp.arange(L)[None, :]
        stage, up, drain, off, hold = (g["stage"], g["up"], g["drain"],
                                       g["off"], g["hold"])
        act = idx < stage[:, None]
        hi = jnp.any((queues > sw["hi_watermark"] * cap) & act, axis=1)
        lo = jnp.all(jnp.where(act, queues < sw["lo_watermark"] * cap,
                               True), axis=1)
        hold = jnp.maximum(hold - 1, 0)
        delay = jnp.maximum(jnp.round(
            self.f(sw["stage_up_delay_ticks"])
            * (1.0 + self.jitter * (2.0 * u_jit - 1.0))), 1.0
        ).astype(jnp.int32)
        can_up = hi & (stage < L) & (up == 0) & (off == 0)
        up = jnp.where(can_up, delay, up)
        drain = jnp.where(hi, False, drain)
        failed = (up == 1) & (u_fail < self.wake_fail)
        fired = (up == 1) & ~failed
        stage = jnp.where(fired, jnp.minimum(stage + 1, L), stage)
        hold = jnp.where(fired, sw["dwell_ticks"], hold)
        up = jnp.maximum(up - 1, 0)
        up = jnp.where(failed, WAKE_RETRY_BACKOFF_TICKS + delay, up)
        drain = drain | (lo & (stage > 1) & ~drain & (up == 0) & (off == 0)
                         & (hold == 0))
        top_q = fig2.lookup(queues, stage - 1)
        begin_off = drain & (top_q <= 0) & (stage > 1)
        stage = jnp.where(begin_off, stage - 1, stage)
        off = jnp.where(begin_off, sw["stage_off_delay_ticks"], off)
        drain = jnp.where(begin_off, False, drain)
        off = jnp.maximum(off - 1, 0)
        # fallback: no usable healthy link while a healthy one exists
        forced = ~jnp.any(usable(stage, drain, L) & ok, axis=1) \
            & jnp.any(ok, axis=1) & self.fallback
        lowest = jnp.argmax(ok, axis=1).astype(jnp.int32)
        stage = jnp.where(forced, jnp.maximum(stage, jnp.minimum(lowest + 1,
                                                                 L)), stage)
        drain = jnp.where(forced, False, drain)
        off = jnp.where(forced, 0, off)
        hold = jnp.where(forced, sw["dwell_ticks"], hold)
        fwake = jnp.where(forced, delay, jnp.maximum(fwake - 1, 0))
        powered = ((idx < stage[:, None])
                   | ((up > 0)[:, None] & (idx == stage[:, None]))
                   | ((off > 0)[:, None] & (idx == stage[:, None]))
                   | (drain[:, None] & (idx == stage[:, None] - 1)))
        out = {"stage": stage.astype(jnp.int32),
               "up": up.astype(jnp.int32), "drain": drain,
               "off": off.astype(jnp.int32), "hold": hold.astype(jnp.int32),
               "powered": powered}
        return out, fwake.astype(jnp.int32), failed, forced

    # ---- one tick ----------------------------------------------------------
    def step(self, kn, st, acc):
        cfg, dt = self.cfg, self.dt
        NCL, RPC, P, NF, R, NC, CUP = (self.NCL, self.RPC, self.P, self.NF,
                                       self.R, self.NC, self.CUP)
        sw, lat = cfg["switch"], cfg["latency"]
        acc = dict(acc)
        rack = jnp.arange(R, dtype=jnp.int32)
        key, k_u, k_z = jax.random.split(st["key"], 3)

        def draws(base, ids, shape, normal=False):
            ks = jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)
            fn = jax.random.normal if normal else jax.random.uniform
            return jax.vmap(lambda k: fn(k, shape))(ks).astype(dt)

        # -- the fault draws: per switch (jitter, failure, link hazards) and
        # per plane domain
        width = 2 + FAULT_DRAW_LINKS
        u_r = draws(jax.random.fold_in(k_u, RSW_FAULT_BRANCH), rack, (width,))
        u_c = draws(jax.random.fold_in(k_u, CSW_FAULT_BRANCH),
                    jnp.arange(NC, dtype=jnp.int32), (width,))
        u_pl = draws(jax.random.fold_in(k_u, RSW_PLANE_BRANCH),
                     jnp.arange(NCL, dtype=jnp.int32), (FAULT_DRAW_LINKS,))
        plane_r = jnp.repeat(u_pl[:, :P], RPC, axis=0)           # (R, P)
        u_fc = jax.random.uniform(jax.random.fold_in(k_u, CSW_PLANE_BRANCH),
                                  (FAULT_DRAW_LINKS,)).astype(dt)
        plane_c = jnp.broadcast_to(u_fc[:CUP], (NC, CUP))
        rsw_ok = st["rsw_timer"] == 0                            # (R, P)
        csw_ok = st["csw_timer"] == 0                            # (NC, CUP)

        # -- rate-based edge: ON/OFF bursts, lognormal flows, paced emission
        u = draws(k_u, rack, (5 + self.F,))
        z = draws(k_z, rack, (2,), normal=True)
        burst = jnp.where(st["burst"], u[:, 0] > kn["p_on_off"],
                          u[:, 1] < kn["p_off_on"])
        spawn = (u[:, 2] < kn["p_spawn"]) & burst
        size_b = jnp.where(u[:, 3] < kn["size_w"],
                           jnp.exp(kn["size_mu1"] + kn["size_s1"] * z[:, 0]),
                           jnp.exp(kn["size_mu2"] + kn["size_s2"] * z[:, 1]))
        size_p = jnp.maximum(
            jnp.ceil(size_b / cfg["rate_edge"]["pkt_bytes"]), 1.0
        ).astype(jnp.int32)
        p_rack = kn["p_intra_rack"]
        p_cl = kn["p_intra_cluster"]
        dest = jnp.where(u[:, 4] < p_rack, 0,
                         jnp.where(u[:, 4] < p_rack + p_cl, 1, 2))
        free = st["rem"] == 0
        slot_ok = spawn & jnp.any(free, axis=1)
        first = jnp.argmax(free, axis=1)
        put = slot_ok[:, None] & (jnp.arange(self.F)[None, :]
                                  == first[:, None])
        rem = st["rem"] + jnp.where(put, size_p[:, None], 0)
        fdest = jnp.where(put, dest[:, None], st["dest"])
        fast = jnp.where(put, (size_p >= kn["elephant_pkts"])[:, None],
                         st["fast"])
        active = rem > 0
        pace = jnp.minimum(
            kn["pace"] * jnp.where(burst, kn["burst_pace_boost"], 1.0), 1.0)
        p_emit = jnp.where(fast, kn["elephant_pace"], pace[:, None])
        emit = active & (u[:, 5:] < p_emit)
        holding = jnp.sum(active, axis=1).astype(dt)
        by_dest = jnp.stack([jnp.sum(emit & (fdest == d), axis=1)
                             for d in range(3)], axis=1).astype(dt)
        rem = jnp.maximum(rem - emit.astype(jnp.int32), 0)
        tick = st["tick"] + 1
        acc["injected"] += jnp.sum(by_dest[:, 1:])

        # -- RSW tier: [intra-cluster, inter-cluster] onto the healthy uplinks
        rg, cg = st["rsw_gate"], st["csw_gate"]
        rsw_q, served, rdrop, rwait = self.tier(
            st["rsw_q"], rg["stage"], rg["drain"], by_dest[:, 1:],
            self.f(sw["rsw_serve_pkts_per_tick"]), rsw_ok)
        acc["drops"] += jnp.sum(rdrop)
        acc["rsw_backlog"] += jnp.sum(rsw_q) + jnp.sum(served)
        acc["rsw_served"] += jnp.sum(served)
        to_csw = jnp.sum(served.reshape(NCL, RPC, P, 2), axis=1)  # (NCL,P,2)
        inter_in = to_csw[..., 1].reshape(NC)

        # down-plane weights: rack r takes plane c with 1/stage(r) if c is
        # one of its active planes (dead or not: the CAMs follow the stage)
        rstage = rg["stage"]
        plane_w = ((jnp.arange(P)[None, :] < rstage[:, None]).astype(dt)
                   / rstage[:, None].astype(dt))                 # (R, P)
        pw = plane_w.reshape(NCL, RPC, P)
        rpc = self.f(RPC)

        intra = jnp.sum(to_csw[..., 0], axis=1)                  # (NCL,)
        csw_down_q = st["csw_down_q"] + (
            intra[:, None, None] / rpc * pw.transpose(0, 2, 1)
        ).reshape(NC, RPC)
        up_share = to_csw[..., 0] / jnp.maximum(intra[:, None],
                                                self.f(1e-9))
        mean_down = jnp.sum(pw, axis=1) / rpc                    # (NCL,P)
        same = jnp.sum(jnp.minimum(up_share, mean_down), axis=1)
        acc["ring_pkts"] += jnp.sum(intra * (1.0 - same))

        # CSW uplinks (40G) to the FCs, dead ones masked
        csw_up_q, cserve, cdrop, cwait = self.tier(
            st["csw_up_q"][..., None], cg["stage"], cg["drain"],
            inter_in[:, None], self.f(sw["csw_serve_pkts_per_tick"]), csw_ok)
        csw_up_q, cserve = csw_up_q[..., 0], cserve[..., 0]
        acc["drops"] += jnp.sum(cdrop)
        acc["csw_up_backlog"] += jnp.sum(st["csw_up_q"])
        acc["csw_up_served"] += jnp.sum(cserve)

        cstage = cg["stage"]
        fc_w = ((jnp.arange(CUP)[None, :] < cstage[:, None]).astype(dt)
                / cstage[:, None].astype(dt))                    # (NC,CUP)
        csw_share = (jnp.sum(pw, axis=1) / rpc).reshape(NC)
        down_cl = jnp.sum(cserve) / self.f(NCL)
        fc_down_q = st["fc_down_q"] + down_cl * csw_share[None, :] * fc_w.T
        # an FC serves down link (f, c) while it is active and csw c's
        # uplink-f transceiver lives (the same fiber)
        fc_act = (jnp.arange(NF)[:, None] < cstage[None, :]) & csw_ok.T
        fserve = jnp.minimum(fc_down_q, self.f(sw["fc_serve_pkts_per_tick"])
                             ) * fc_act
        fc_down_q = fc_down_q - fserve
        stranded = jnp.where(fc_act, 0.0, fc_down_q)
        n_str = jnp.sum(stranded)
        fc_ring = self.f(cfg["site"]["fc_ring_links"]
                         * sw["ring_pkts_per_tick_per_link"])
        mig = jnp.minimum(n_str, fc_ring)
        moved = stranded * jnp.minimum(mig / jnp.maximum(n_str, self.f(1e-9)),
                                       1.0)
        fc_down_q = fc_down_q - moved
        fc_down_q = fc_down_q.at[0, :].add(jnp.sum(moved, axis=0))
        acc["fc_ring_pkts"] += mig
        acc["fc_backlog"] += jnp.sum(st["fc_down_q"])
        acc["fc_served"] += jnp.sum(fserve)

        per_csw = jnp.sum(fserve, axis=0)                        # (NC,)
        w_cr = pw.transpose(0, 2, 1).reshape(NC, RPC)
        row_w = jnp.sum(w_cr, axis=1)
        w_norm = w_cr / jnp.maximum(row_w[:, None], self.f(1e-9))
        routable = row_w > 0
        csw_down_q = csw_down_q + jnp.where(routable, per_csw, 0.0)[:, None] \
            * w_norm
        orphan = jnp.sum(jnp.where(routable, 0.0, per_csw).reshape(NCL, P),
                         axis=1)
        dq = csw_down_q.reshape(NCL, P, RPC)
        dq = dq.at[:, 0, :].add(orphan[:, None]
                                * w_norm.reshape(NCL, P, RPC)[:, 0, :])
        acc["ring_pkts"] += jnp.sum(orphan)

        # CSW down links to the racks: served on the rack's active planes
        # whose uplink transceiver lives; stranded backlog rides the ring
        ok_pl = rsw_ok.reshape(NCL, RPC, P).transpose(0, 2, 1)   # (NCL,P,RPC)
        down_act = (jnp.arange(P)[None, :, None]
                    < rstage.reshape(NCL, RPC)[:, None, :]) & ok_pl
        dserve = jnp.minimum(dq, self.f(sw["csw_down_serve_pkts_per_tick"])
                             ) * down_act
        dq = dq - dserve
        str_d = jnp.where(down_act, 0.0, dq)
        tot = jnp.sum(str_d, axis=(1, 2))
        csw_ring = self.f(cfg["site"]["csw_ring_links"]
                          * sw["ring_pkts_per_tick_per_link"])
        migd = jnp.minimum(tot, csw_ring)
        mv = str_d * jnp.minimum(migd / jnp.maximum(tot, self.f(1e-9)),
                                 1.0)[:, None, None]
        dq = dq - mv
        dq = dq.at[:, 0, :].add(jnp.sum(mv, axis=1))
        csw_down_q = dq.reshape(NC, RPC)
        acc["ring_pkts"] += jnp.sum(migd)
        acc["csw_down_backlog"] += jnp.sum(st["csw_down_q"])
        acc["csw_down_served"] += jnp.sum(dserve)
        delivered_r = jnp.sum(dserve, axis=1).reshape(R)

        spr = self.f(self.spr)
        need = jnp.minimum(holding + delivered_r, spr)
        node_on = jnp.maximum(
            need, st["node_on"] - spr / cfg["node"]["idle_timeout_ticks"])
        acc["node_on"] += jnp.sum(node_on)

        # one delay sample per rack and class: queue waits, the wake stall
        # and the fault stall of the switches on the path
        down_rc = dq.transpose(0, 2, 1).reshape(R, P)
        win = inter_in.reshape(NCL, P)

        def cl_avg(x):
            return jnp.sum(win * x.reshape(NCL, P), axis=1) \
                / jnp.maximum(jnp.sum(win, axis=1), self.f(1e-9))

        def per_rack(x):
            return jnp.repeat(x, RPC)

        gon = kn["gating"] > 0

        def stall(x):
            return jnp.where(gon, x.astype(dt), 0.0)

        stall_r, stall_c = stall(rg["up"]), stall(cg["up"])
        fstall_r, fstall_c = stall(st["rsw_fwake"]), stall(st["csw_fwake"])
        fc_cap = self.f(sw["fc_serve_pkts_per_tick"]) * jnp.sum(
            fc_act.astype(dt))
        fc_wait = jnp.sum(fc_down_q) / jnp.maximum(fc_cap, self.f(1e-9))
        q_i = rwait + jnp.sum(plane_w * down_rc, axis=1)
        q_x = q_i + per_rack(cl_avg(cwait)) + fc_wait
        f_i = fstall_r
        f_x = fstall_r + per_rack(cl_avg(fstall_c))
        base_i = lat["stack_us"] + 4.0 * lat["wire_hop_us"]
        d_i = base_i + q_i + stall_r + f_i
        d_x = base_i + 2.0 * lat["wire_hop_us"] + q_x \
            + (stall_r + per_rack(cl_avg(stall_c))) + f_x
        w_i, w_x = by_dest[:, 1], by_dest[:, 2]
        hist = self.hist_add(acc["delay_hist"], d_i, w_i, cfg["delay_hist"])
        acc["delay_hist"] = self.hist_add(hist, d_x, w_x, cfg["delay_hist"])
        acc["delay_sum"] += jnp.sum(w_i * d_i) + jnp.sum(w_x * d_x)
        acc["delay_wt"] += jnp.sum(w_i) + jnp.sum(w_x)
        acc["delay_fault_sum"] += jnp.sum(w_i * f_i) + jnp.sum(w_x * f_x)

        # -- hard faults strike powered healthy links, gated or not; a dying
        # link's queue is lost to the fault-drop bin
        rsw_timer, rsw_new = self.faults(st["rsw_timer"], u_r[:, 2:2 + P],
                                         plane_r, rg["powered"])
        csw_timer, csw_new = self.faults(st["csw_timer"], u_c[:, 2:2 + CUP],
                                         plane_c, cg["powered"])
        acc["fault_drops"] += (
            jnp.sum(jnp.where(rsw_new[..., None], rsw_q, 0.0))
            + jnp.sum(jnp.where(csw_new, csw_up_q, 0.0)))
        rsw_q = jnp.where(rsw_new[..., None], 0.0, rsw_q)
        csw_up_q = jnp.where(csw_new, 0.0, csw_up_q)
        acc["fault_link_ticks"] += (jnp.sum(rsw_timer > 0)
                                    + jnp.sum(csw_timer > 0)).astype(dt)

        # -- the controllers on all of a switch's output queues
        rsw_live, csw_live = rsw_timer == 0, csw_timer == 0
        rg_new, rfw, rfail, rforced = self.gate(
            rg, jnp.maximum(jnp.sum(rsw_q, axis=2), down_rc), rsw_live,
            u_r[:, 0], u_r[:, 1], st["rsw_fwake"])
        cg_new, cfw, cfail, cforced = self.gate(
            cg, jnp.maximum(csw_up_q, fc_down_q.T), csw_live,
            u_c[:, 0], u_c[:, 1], st["csw_fwake"])
        rg = jax.tree.map(lambda a, b: jnp.where(gon, a, b), rg_new, rg)
        cg = jax.tree.map(lambda a, b: jnp.where(gon, a, b), cg_new, cg)
        rfw, cfw = jnp.where(gon, rfw, 0), jnp.where(gon, cfw, 0)
        acc["wake_retries"] += jnp.where(
            gon, jnp.sum(rfail) + jnp.sum(cfail), 0).astype(dt)
        acc["forced_wakes"] += jnp.where(
            gon, jnp.sum(rforced) + jnp.sum(cforced), 0).astype(dt)

        # -- connectivity audit on the end-of-tick state, and power: a dead
        # transceiver is dark
        def lost(g, live, n_links):
            use = usable(g["stage"], g["drain"], n_links) & live
            return jnp.sum(jnp.any(live, axis=1) & ~jnp.any(use, axis=1))

        acc["conn_loss"] += (lost(rg, rsw_live, P)
                             + lost(cg, csw_live, CUP)).astype(dt)
        acc["rsw_powered"] += jnp.sum(rg["powered"] & rsw_live).astype(dt)
        acc["csw_powered"] += jnp.sum(cg["powered"] & csw_live).astype(dt)

        st = {"key": key, "burst": burst, "rem": rem, "dest": fdest,
              "fast": fast, "tick": tick,
              **{k: st[k] for k in ("ft_start", "ft_rem", "ft_size",
                                    "ft_dst", "ft_cwnd")},
              "rsw_q": rsw_q, "csw_up_q": csw_up_q, "csw_down_q": csw_down_q,
              "fc_down_q": fc_down_q, "rsw_gate": rg, "csw_gate": cg,
              "node_on": node_on, "rsw_timer": rsw_timer, "rsw_fwake": rfw,
              "csw_timer": csw_timer, "csw_fwake": cfw}
        return st, acc


def metrics(cfg: dict, row: dict, a: dict, n_ticks: int) -> dict:
    """fig2's metrics of one scenario, with the fault model's."""
    out = fig2.metrics(cfg, row, a, n_ticks)
    s = cfg["site"]
    NC = s["n_clusters"] * s["csw_per_cluster"]
    links = s["n_clusters"] * s["racks_per_cluster"] * s["csw_per_cluster"] \
        + NC * s["n_fc"]
    inj = max(float(a["injected"]), 1e-9)
    out.update({
        "fault_dropped_pkts": float(a["fault_drops"]),
        "fault_drop_frac": float(a["fault_drops"]) / inj,
        "link_fault_frac": float(a["fault_link_ticks"])
        / (float(n_ticks) * links),
        "wake_retries": float(a["wake_retries"]),
        "forced_wakes": float(a["forced_wakes"]),
        "conn_loss_ticks": float(a["conn_loss"]),
        "delay_fault_stall_us": float(a["delay_fault_sum"])
        / max(float(a["delay_wt"]), 1e-9),
        "delivered_frac": float(a["csw_down_served"]) / inj,
    })
    return out


_REFS: dict = {}


def reference_metrics(cfg, rows, seeds, n_ticks, dtype=jnp.float32,
                      block=1000):
    """Metrics of each (row, seed) after ``n_ticks``, rows run together."""
    key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name)
    if key not in _REFS:
        _REFS[key] = FaultRef(cfg, dtype)
    ref = _REFS[key]
    with jax.default_matmul_precision("highest"):
        accs = ref.run(rows, seeds, n_ticks, block=block)
    return [metrics(cfg, r, a, n_ticks) for r, a in zip(rows, accs)]
