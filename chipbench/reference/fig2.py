"""Plain reference of the Fig 2 LC/DC site simulator (one scenario).

A straightforward implementation of the semantics the sweep engine
states for the configurations ``fig2_rate`` and ``fig2_flows``: a 1 us
slotted site of racks, cluster switches (CSWs) and fabric cores (FCs);
a rate-based or flow-level traffic edge; min-backlog enqueue and
watermark stage gating at the RSW and CSW tiers; ring migration of
packets stranded on gated planes; server-link gating; delay and
flow-completion-time histograms; accumulators folded on the host in
float64; and the paper's metrics computed from them.

It imports nothing of the program and reads every number from the
configuration file. It is written for one scenario with plain jnp:
no kernel, no padded hull, no multi-site masks and no device fold.
The optics are perfect (the configurations say so), so the fault model
is the identity and is left out. The random streams are the ones the
configuration's seed defines: per tick the key splits into (next,
k_u, k_z), and every per-rack draw is keyed by ``fold_in`` of the
rack's id, with the flow engine on the fixed branches 0x7F000003/4.

``dtype`` selects the precision of every float value (draws, queues,
accumulators): float32 is the configuration's own, and bfloat16 is the
control that the comparison must reject.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1e30
FLOW_ARRIVAL_BRANCH = 0x7F000003
FLOW_SIZE_BRANCH = 0x7F000004

#: the scalar accumulators (the two histograms are added in zero_acc)
ACC_KEYS = (
    "injected", "drops", "rsw_backlog", "rsw_served", "csw_up_backlog",
    "csw_up_served", "csw_down_backlog", "csw_down_served", "fc_backlog",
    "fc_served", "ring_pkts", "fc_ring_pkts", "rsw_powered",
    "csw_powered", "node_on", "delay_sum", "delay_wt", "flows_started",
    "flows_completed", "flows_evicted", "fct_sum")


def pick(idx, n):
    """One-hot (..., n) of integer indices. Table lookups and scatters go
    through it: a TPU serializes gathers and scatters, and a masked sum
    of one value and zeros is exact."""
    return idx[..., None] == jnp.arange(n)


def lookup(table, idx):
    """``table[..., idx]`` along the last axis, as a masked sum."""
    return jnp.sum(jnp.where(pick(idx, table.shape[-1]), table, 0), axis=-1)


def scenario_knobs(cfg: dict, row: dict) -> dict:
    """Per-scenario numbers of one traffic row, in float64."""
    tr = cfg["traces"][row["trace"]]
    spr = cfg["site"]["servers_per_rack"]
    mean_iat = math.exp(tr["iat_mu"] + tr["iat_s"] ** 2 / 2)
    duty = tr["p_off_on"] / (tr["p_off_on"] + tr["p_on_off"])
    rack_rate = spr / mean_iat / max(duty, 1e-6)
    rs = float(row.get("rate_scale", 1.0))
    flow_mode = int(row.get("flow_mode", 0))
    rate = float(row.get("flow_arrival_rate", 0.0))
    dists = list(cfg.get("flows", {}).get("size_cdfs", {"websearch": 0}))
    return {
        "p_spawn": min(rack_rate * rs, 1.0),
        "flow_rate": rate if rate > 0.0 else min(rack_rate * rs, 1.0),
        "flow_on": float(flow_mode == 1),
        "flow_dist": dists.index(row.get("flow_size_dist", dists[0])),
        "incast": int(row.get("incast_degree", 1)),
        "flow_cap": int(row.get("flow_table_cap",
                                cfg.get("flows", {}).get("table_slots", 1))),
        "gating": float(bool(row["gating"])),
        **{k: tr[k] for k in (
            "p_on_off", "p_off_on", "size_w", "size_mu1", "size_s1",
            "size_mu2", "size_s2", "p_intra_rack", "p_intra_cluster",
            "pace", "burst_pace_boost", "elephant_pkts",
            "elephant_pace")},
    }


def _knob_arrays(cfg, rows, dtype):
    knobs = [scenario_knobs(cfg, r) for r in rows]
    ints = ("flow_dist", "incast", "flow_cap", "elephant_pkts")
    out = {}
    for k in knobs[0]:
        v = np.asarray([kn[k] for kn in knobs], np.float64)
        if k in ints:
            out[k] = jnp.asarray(v.astype(np.int32))
        else:
            # the program takes its knobs in float32; a lower precision
            # rounds from there
            out[k] = jnp.asarray(v.astype(np.float32)).astype(dtype)
    return out


class Ref:
    """The reference for one configuration at one precision."""

    def __init__(self, cfg: dict, dtype=jnp.float32):
        self.cfg = cfg
        self.dt = dtype
        s = cfg["site"]
        self.NCL, self.RPC = s["n_clusters"], s["racks_per_cluster"]
        self.P, self.NF = s["csw_per_cluster"], s["n_fc"]
        self.R, self.NC = self.NCL * self.RPC, self.NCL * self.P
        self.CUP = self.NF
        self.spr = float(s["servers_per_rack"])
        self.flows = cfg.get("flows")
        self.FT = self.flows["table_slots"] if self.flows else 1
        self.W = self.flows["max_incast_degree"] if self.flows else 1
        self.F = cfg["rate_edge"]["flow_slots"]
        self._compiled = {}
        if self.flows:
            tabs = list(self.flows["size_cdfs"].values())
            width = max(len(t) for t in tabs)
            tabs = [list(t) + [t[-1]] * (width - len(t)) for t in tabs]
            self.cdf_s = jnp.asarray([[a for a, _ in t] for t in tabs],
                                     jnp.float32).astype(dtype)
            self.cdf_p = jnp.asarray([[b for _, b in t] for t in tabs],
                                     jnp.float32).astype(dtype)

    # ---- helpers ---------------------------------------------------------
    def f(self, x):
        return jnp.asarray(x, self.dt)

    def init(self, knobs, seed):
        R, P, NC, CUP = self.R, self.P, self.NC, self.CUP
        g = knobs["gating"] > 0
        dt = self.dt

        def gate(n, links):
            stage = jnp.where(g, 1, links).astype(jnp.int32)
            idx = jnp.arange(links)[None, :]
            powered = jnp.where(g, idx == 0, True) & jnp.ones((n, 1), bool)
            z = jnp.zeros((n,), jnp.int32)
            return {"stage": jnp.broadcast_to(stage, (n,)), "up": z,
                    "drain": jnp.zeros((n,), bool), "off": z, "hold": z,
                    "powered": powered}

        FT = self.FT
        return {
            "key": jax.random.PRNGKey(seed),
            "burst": jnp.ones((R,), bool),
            "rem": jnp.zeros((R, self.F), jnp.int32),
            "dest": jnp.zeros((R, self.F), jnp.int32),
            "fast": jnp.zeros((R, self.F), bool),
            "tick": jnp.zeros((), jnp.int32),
            "ft_start": jnp.zeros((R, FT), jnp.int32),
            "ft_rem": jnp.zeros((R, FT), dt),
            "ft_size": jnp.zeros((R, FT), jnp.int32),
            "ft_dst": jnp.zeros((R, FT), jnp.int32),
            "ft_cwnd": jnp.zeros((R, FT), dt),
            "rsw_q": jnp.zeros((R, P, 2), dt),
            "csw_up_q": jnp.zeros((NC, CUP), dt),
            "csw_down_q": jnp.zeros((NC, self.RPC), dt),
            "fc_down_q": jnp.zeros((self.NF, NC), dt),
            "rsw_gate": gate(R, P), "csw_gate": gate(NC, CUP),
            "node_on": jnp.zeros((R,), dt),
        }

    def zero_acc(self):
        cfg = self.cfg
        acc = {k: jnp.zeros((), self.dt) for k in ACC_KEYS}
        acc["delay_hist"] = jnp.zeros((cfg["delay_hist"]["bins"],), self.dt)
        fb = self.flows["fct_hist"]["bins"] if self.flows else 1
        acc["fct_hist"] = jnp.zeros((fb,), self.dt)
        return acc

    def hist_add(self, hist, d, w, frame):
        lo, bins, bpo = frame["min_us"], frame["bins"], frame["bins_per_octave"]
        idx = jnp.floor(jnp.log2(jnp.maximum(d, 1e-9) / lo) * bpo + 1e-4)
        idx = jnp.clip(idx, -1, bins - 2).astype(jnp.int32) + 1
        return hist + jnp.sum(jnp.where(pick(idx, bins), w[:, None], 0.0),
                              axis=0)

    # ---- one switch tier: min-backlog enqueue, capacity clamp, serve ------
    def tier(self, q, stage, drain, arr, serve_rate):
        """q (S, L, K), arr (S, K). Returns (q, served, dropped, wait)."""
        cap = self.f(self.cfg["switch"]["queue_cap_pkts"])
        L = q.shape[1]
        idx = jnp.arange(L)[None, :]
        act = idx < stage[:, None]
        top = idx == stage[:, None] - 1
        usable = act & ~(drain[:, None] & top & (stage[:, None] > 1))
        qtot = jnp.sum(q, axis=2)
        masked = jnp.where(usable, qtot, self.f(BIG))
        port = jnp.argmin(masked, axis=1)                 # lowest on ties
        mn = jnp.min(masked, axis=1)
        wait = mn / serve_rate
        add = jnp.sum(arr, axis=1)
        room = jnp.maximum(cap - mn, 0.0)
        scale = jnp.minimum(1.0, room / jnp.maximum(add, self.f(1e-9)))
        dropped = add * (1.0 - scale)
        onehot = (idx == port[:, None]).astype(q.dtype)
        q = q + onehot[:, :, None] * (arr * scale[:, None])[:, None, :]
        qtot = jnp.sum(q, axis=2)
        serve = jnp.minimum(qtot, serve_rate) * act
        frac = jnp.minimum(serve / jnp.maximum(qtot, self.f(1e-9)), 1.0)
        served = q * frac[:, :, None]
        return q - served, served, dropped, wait

    def gate(self, g, queues):
        """One watermark controller tick on monitored backlogs (S, L)."""
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
        can_up = hi & (stage < L) & (up == 0) & (off == 0)
        up = jnp.where(can_up, sw["stage_up_delay_ticks"], up)
        drain = jnp.where(hi, False, drain)
        fired = up == 1
        stage = jnp.where(fired, jnp.minimum(stage + 1, L), stage)
        hold = jnp.where(fired, sw["dwell_ticks"], hold)
        up = jnp.maximum(up - 1, 0)
        drain = drain | (lo & (stage > 1) & ~drain & (up == 0) & (off == 0)
                         & (hold == 0))
        top_q = lookup(queues, stage - 1)
        begin_off = drain & (top_q <= 0) & (stage > 1)
        stage = jnp.where(begin_off, stage - 1, stage)
        off = jnp.where(begin_off, sw["stage_off_delay_ticks"], off)
        drain = jnp.where(begin_off, False, drain)
        off = jnp.maximum(off - 1, 0)
        powered = ((idx < stage[:, None])
                   | ((up > 0)[:, None] & (idx == stage[:, None]))
                   | ((off > 0)[:, None] & (idx == stage[:, None]))
                   | (drain[:, None] & (idx == stage[:, None] - 1)))
        return {"stage": stage.astype(jnp.int32),
                "up": up.astype(jnp.int32), "drain": drain,
                "off": off.astype(jnp.int32), "hold": hold.astype(jnp.int32),
                "powered": powered}

    # ---- one tick ----------------------------------------------------------
    def step(self, kn, st, acc):
        cfg, dt = self.cfg, self.dt
        NCL, RPC, P, NF, R, NC, CUP = (self.NCL, self.RPC, self.P, self.NF,
                                       self.R, self.NC, self.CUP)
        sw, lat = cfg["switch"], cfg["latency"]
        cap = self.f(sw["queue_cap_pkts"])
        acc = dict(acc)
        rack = jnp.arange(R, dtype=jnp.int32)
        key, k_u, k_z = jax.random.split(st["key"], 3)

        def draws(base, shape, normal=False):
            ks = jax.vmap(lambda i: jax.random.fold_in(base, i))(rack)
            fn = jax.random.normal if normal else jax.random.uniform
            return jax.vmap(lambda k: fn(k, shape))(ks).astype(dt)

        # -- rate-based edge: ON/OFF bursts, lognormal flows, paced emission
        u = draws(k_u, (5 + self.F,))
        z = draws(k_z, (2,), normal=True)
        burst = jnp.where(st["burst"], u[:, 0] > kn["p_on_off"],
                          u[:, 1] < kn["p_off_on"])
        spawn = (u[:, 2] < kn["p_spawn"]) & burst & (kn["flow_on"] == 0)
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

        # -- flow engine: table admission, AIMD windows, fluid emission
        tick = st["tick"] + 1
        ft = {k: st[k] for k in ("ft_start", "ft_rem", "ft_size", "ft_dst",
                                 "ft_cwnd")}
        done = jnp.zeros((R, self.FT), bool)
        if self.flows:
            fl = self.flows
            flow_on = kn["flow_on"] > 0
            ua = draws(jax.random.fold_in(k_u, FLOW_ARRIVAL_BRANCH), (2,))
            us = draws(jax.random.fold_in(k_u, FLOW_SIZE_BRANCH), (self.W,))
            arrive = (ua[:, 0] < kn["flow_rate"]) & flow_on
            n_new = jnp.where(arrive, kn["incast"], 0)
            # inverse-CDF sizes, log-linear between anchors
            tab_s = lookup(self.cdf_s.T, kn["flow_dist"])
            tab_p = lookup(self.cdf_p.T, kn["flow_dist"])
            npts = tab_p.shape[0]
            seg = jnp.clip(jnp.sum(us[..., None] >= tab_p, axis=-1) - 1,
                           0, npts - 2)
            s0, s1 = lookup(tab_s, seg), lookup(tab_s, seg + 1)
            p0, p1 = lookup(tab_p, seg), lookup(tab_p, seg + 1)
            fr = jnp.clip((us - p0) / jnp.maximum(p1 - p0, self.f(1e-9)),
                          0.0, 1.0)
            sizes = jnp.maximum(jnp.ceil(s0 * (s1 / s0) ** fr), 1.0)
            fdst = jnp.where(ua[:, 1] < p_rack, 0,
                             jnp.where(ua[:, 1] < p_rack + p_cl, 1, 2))
            usable = jnp.arange(self.FT)[None, :] < kn["flow_cap"]
            live0 = (ft["ft_rem"] > 0) & usable
            slot_free = ~live0 & usable
            # the k-th new flow of a burst takes the k-th free slot
            rank = jnp.cumsum(slot_free.astype(jnp.int32), axis=1) - 1
            k_new = jnp.where(slot_free & (rank < n_new[:, None]), rank, -1)
            placed = k_new >= 0
            new_sz = lookup(sizes[:, None, :], jnp.maximum(k_new, 0))
            admitted = jnp.sum(placed, axis=1)
            # AIMD on the previous tick's rack congestion signal
            rstage = st["rsw_gate"]["stage"]
            cong = jnp.any((jnp.sum(st["rsw_q"], axis=2)
                            > sw["hi_watermark"] * cap)
                           & (jnp.arange(P)[None, :] < rstage[:, None]),
                           axis=1)
            cw = ft["ft_cwnd"]
            cw = jnp.where(live0, jnp.where(
                cong[:, None],
                jnp.maximum(cw * fl["aimd_decrease"], fl["cwnd_min"]),
                jnp.minimum(cw + fl["aimd_increase"],
                            fl["line_rate_pkts_per_tick"])), cw)
            ft = {"ft_start": jnp.where(placed, tick, ft["ft_start"]),
                  "ft_rem": jnp.where(placed, new_sz, ft["ft_rem"]),
                  "ft_size": jnp.where(placed, new_sz.astype(jnp.int32),
                                       ft["ft_size"]),
                  "ft_dst": jnp.where(placed, fdst[:, None], ft["ft_dst"]),
                  "ft_cwnd": jnp.where(placed, self.f(fl["cwnd_init"]), cw)}
            live = (ft["ft_rem"] > 0) & usable
            sent = jnp.where(live, jnp.minimum(ft["ft_rem"], ft["ft_cwnd"]),
                             0.0)
            ft["ft_rem"] = ft["ft_rem"] - sent
            done = live & (ft["ft_rem"] <= 0)
            f_dest = jnp.stack([jnp.sum(jnp.where(ft["ft_dst"] == d, sent,
                                                  0.0), axis=1)
                                for d in range(3)], axis=1)
            by_dest = jnp.where(flow_on, f_dest, by_dest)
            holding = jnp.where(flow_on, jnp.sum(live, axis=1).astype(dt),
                                holding)
            acc["flows_started"] += jnp.sum(n_new).astype(dt)
            acc["flows_evicted"] += (jnp.sum(n_new)
                                     - jnp.sum(admitted)).astype(dt)
        acc["injected"] += jnp.sum(by_dest[:, 1:])

        # -- RSW tier: [intra-cluster, inter-cluster] onto the uplinks
        rg, cg = st["rsw_gate"], st["csw_gate"]
        rsw_q, served, rdrop, rwait = self.tier(
            st["rsw_q"], rg["stage"], rg["drain"], by_dest[:, 1:],
            self.f(sw["rsw_serve_pkts_per_tick"]))
        acc["drops"] += jnp.sum(rdrop)
        acc["rsw_backlog"] += jnp.sum(rsw_q) + jnp.sum(served)
        acc["rsw_served"] += jnp.sum(served)
        to_csw = jnp.sum(served.reshape(NCL, RPC, P, 2), axis=1)  # (NCL,P,2)
        inter_in = to_csw[..., 1].reshape(NC)

        # down-plane weights: rack r takes plane c with 1/stage(r) if c is
        # one of its active planes
        rstage = rg["stage"]
        plane_w = ((jnp.arange(P)[None, :] < rstage[:, None]).astype(dt)
                   / rstage[:, None].astype(dt))                 # (R, P)
        pw = plane_w.reshape(NCL, RPC, P)
        rpc = self.f(RPC)

        # CSW: intra-cluster traffic to the down queues, ring charge for
        # the share whose up plane is not its down plane
        intra = jnp.sum(to_csw[..., 0], axis=1)                  # (NCL,)
        csw_down_q = st["csw_down_q"] + (
            intra[:, None, None] / rpc * pw.transpose(0, 2, 1)
        ).reshape(NC, RPC)
        up_share = to_csw[..., 0] / jnp.maximum(intra[:, None],
                                                self.f(1e-9))
        mean_down = jnp.sum(pw, axis=1) / rpc                    # (NCL,P)
        same = jnp.sum(jnp.minimum(up_share, mean_down), axis=1)
        acc["ring_pkts"] += jnp.sum(intra * (1.0 - same))

        # CSW uplinks (40G) to the FCs
        csw_up_q, cserve, cdrop, cwait = self.tier(
            st["csw_up_q"][..., None], cg["stage"], cg["drain"],
            inter_in[:, None], self.f(sw["csw_serve_pkts_per_tick"]))
        csw_up_q, cserve = csw_up_q[..., 0], cserve[..., 0]
        acc["drops"] += jnp.sum(cdrop)
        acc["csw_up_backlog"] += jnp.sum(st["csw_up_q"])
        acc["csw_up_served"] += jnp.sum(cserve)

        # FCs: inter-cluster traffic splits evenly over the clusters, then
        # over the CSW planes its racks ride, over each CSW's active uplinks
        cstage = cg["stage"]
        fc_w = ((jnp.arange(CUP)[None, :] < cstage[:, None]).astype(dt)
                / cstage[:, None].astype(dt))                    # (NC,CUP)
        csw_share = (jnp.sum(pw, axis=1) / rpc).reshape(NC)
        down_cl = jnp.sum(cserve) / self.f(NCL)
        fc_down_q = st["fc_down_q"] + down_cl * csw_share[None, :] * fc_w.T
        fc_act = jnp.arange(NF)[:, None] < cstage[None, :]        # (NF,NC)
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

        # FC-served packets land on each CSW's down queues, split over the
        # racks riding that plane; a plane no rack rides sends its share
        # over the cluster ring to plane 0
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

        # CSW down links to the racks: served on the rack's active planes,
        # stranded backlog rides the cluster ring to plane 0
        down_act = (jnp.arange(P)[None, :, None]
                    < rstage.reshape(NCL, RPC)[:, None, :])      # (NCL,P,RPC)
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

        # server links: held on while busy, with an idle timeout
        spr = self.f(self.spr)
        need = jnp.minimum(holding + delivered_r, spr)
        node_on = jnp.maximum(
            need, st["node_on"] - spr / cfg["node"]["idle_timeout_ticks"])
        acc["node_on"] += jnp.sum(node_on)

        # one delay sample per rack and class for this tick's packets
        down_rc = dq.transpose(0, 2, 1).reshape(R, P)
        win = inter_in.reshape(NCL, P)

        def cl_avg(x):
            return jnp.sum(win * x.reshape(NCL, P), axis=1) \
                / jnp.maximum(jnp.sum(win, axis=1), self.f(1e-9))

        def per_rack(x):
            return jnp.repeat(x, RPC)

        gon = kn["gating"] > 0
        stall_r = jnp.where(gon, rg["up"].astype(dt), 0.0)
        stall_c = jnp.where(gon, cg["up"].astype(dt), 0.0)
        fc_cap = self.f(sw["fc_serve_pkts_per_tick"]) * jnp.sum(
            fc_act.astype(dt))
        fc_wait = jnp.sum(fc_down_q) / jnp.maximum(fc_cap, self.f(1e-9))
        q_i = rwait + jnp.sum(plane_w * down_rc, axis=1)
        q_x = q_i + per_rack(cl_avg(cwait)) + fc_wait
        base_i = lat["stack_us"] + 4.0 * lat["wire_hop_us"]
        d_i = base_i + q_i + stall_r
        d_x = base_i + 2.0 * lat["wire_hop_us"] + q_x \
            + (stall_r + per_rack(cl_avg(stall_c)))
        w_i, w_x = by_dest[:, 1], by_dest[:, 2]
        hist = self.hist_add(acc["delay_hist"], d_i, w_i, cfg["delay_hist"])
        acc["delay_hist"] = self.hist_add(hist, d_x, w_x, cfg["delay_hist"])
        acc["delay_sum"] += jnp.sum(w_i * d_i) + jnp.sum(w_x * d_x)
        acc["delay_wt"] += jnp.sum(w_i) + jnp.sum(w_x)

        if self.flows:
            # flow completion: table residence plus this tick's path delay
            wdone = done.astype(dt)
            res_t = (tick - ft["ft_start"] + 1).astype(dt) * lat["tick_us"]
            path = jnp.where(ft["ft_dst"] == 2, d_x[:, None],
                             jnp.where(ft["ft_dst"] == 1, d_i[:, None],
                                       self.f(lat["stack_us"])))
            fct = res_t + path
            acc["fct_hist"] = self.hist_add(
                acc["fct_hist"], fct.reshape(-1), wdone.reshape(-1),
                self.flows["fct_hist"])
            acc["flows_completed"] += jnp.sum(wdone)
            acc["fct_sum"] += jnp.sum(fct * wdone)

        # watermark controllers on all of a switch's output queues
        rg_new = self.gate(rg, jnp.maximum(jnp.sum(rsw_q, axis=2), down_rc))
        cg_new = self.gate(cg, jnp.maximum(csw_up_q, fc_down_q.T))
        rg = jax.tree.map(lambda a, b: jnp.where(gon, a, b), rg_new, rg)
        cg = jax.tree.map(lambda a, b: jnp.where(gon, a, b), cg_new, cg)
        acc["rsw_powered"] += jnp.sum(rg["powered"]).astype(dt)
        acc["csw_powered"] += jnp.sum(cg["powered"]).astype(dt)

        st = {"key": key, "burst": burst, "rem": rem, "dest": fdest,
              "fast": fast, "tick": tick, **ft, "rsw_q": rsw_q,
              "csw_up_q": csw_up_q, "csw_down_q": csw_down_q,
              "fc_down_q": fc_down_q, "rsw_gate": rg, "csw_gate": cg,
              "node_on": node_on}
        return st, acc

    # ---- a run -------------------------------------------------------------
    def run(self, rows, seeds, n_ticks, block=1000):
        """Accumulators of each row (a dict of float64 numpy arrays)
        after ``n_ticks``; in-scan sums restart every ``block`` ticks
        and are added up on the host in float64."""
        kn = _knob_arrays(self.cfg, rows, self.dt)
        keys = np.asarray([s & 0xFFFFFFFF for s in seeds], np.uint32)
        st = jax.vmap(self.init)(kn, jnp.asarray(keys))
        zero = jax.vmap(lambda _: self.zero_acc())(jnp.arange(len(rows)))
        tot = None
        done = 0
        while done < n_ticks:
            n = min(block, n_ticks - done)
            st, acc = self._run_block(kn, st, zero, n)
            acc = jax.device_get(acc)
            acc = {k: np.asarray(v, np.float64) for k, v in acc.items()}
            tot = acc if tot is None else {k: tot[k] + acc[k] for k in tot}
            done += n
        tot.update({k: np.asarray(v, np.float64)
                    for k, v in jax.device_get(self.held(kn, st)).items()})
        return [{k: v[i] for k, v in tot.items()} for i in range(len(rows))]

    def held(self, kn, st):
        """What the site holds at the end of a run, per row: packets in
        the four queues, and live flows in the usable table slots."""
        def total(x):
            return jnp.sum(x.reshape(x.shape[0], -1).astype(jnp.float32),
                           axis=1)
        usable = jnp.arange(self.FT)[None, None, :] < \
            kn["flow_cap"][:, None, None]
        return {"in_flight": sum(total(st[q]) for q in (
                    "rsw_q", "csw_up_q", "csw_down_q", "fc_down_q")),
                "in_table": total((st["ft_rem"] > 0) & usable)}

    def _run_block(self, kn, st, zero, n):
        fn = self._compiled.get(n)
        if fn is None:
            # one program per block length, kept for the next run
            step = jax.vmap(self.step)

            def go(kn, st, acc):
                def body(carry, _):
                    s, a = carry
                    return step(kn, s, a), None
                (s, a), _ = jax.lax.scan(body, (st, acc), None, length=n)
                return s, a
            fn = self._compiled[n] = jax.jit(go)
        return fn(kn, st, zero)


def _quantile(hist, q, lo, bins, bpo):
    edges = np.concatenate([[0.0], lo * 2.0 ** (np.arange(bins) / bpo)])
    total = float(np.sum(hist))
    if total <= 0.0:
        return 0.0
    cdf = np.cumsum(hist) / total
    i = min(int(np.searchsorted(cdf, q)), len(hist) - 1)
    prev = float(cdf[i - 1]) if i > 0 else 0.0
    frac = min(max((q - prev) / max(float(cdf[i]) - prev, 1e-12), 0.0), 1.0)
    if edges[i] <= 0.0:
        return float(edges[i + 1] * frac)
    return float(edges[i] * (edges[i + 1] / edges[i]) ** frac)


def metrics(cfg: dict, row: dict, a: dict, n_ticks: int) -> dict:
    """The paper's metrics of one scenario from its accumulators."""
    s, lat, pw = cfg["site"], cfg["latency"], cfg["power_w"]
    T = float(n_ticks)
    R = s["n_clusters"] * s["racks_per_cluster"]
    NC = s["n_clusters"] * s["csw_per_cluster"]
    rsw_links = R * s["csw_per_cluster"]
    csw_links = NC * s["n_fc"]

    def wait(b, n):
        return float(b) / max(float(n), 1e-9)

    inj = max(float(a["injected"]), 1e-9)
    inter = float(a["csw_up_served"]) / inj
    mean_wait = (wait(a["rsw_backlog"], a["rsw_served"])
                 + wait(a["csw_down_backlog"], a["csw_down_served"])
                 + inter * (wait(a["csw_up_backlog"], a["csw_up_served"])
                            + wait(a["fc_backlog"], a["fc_served"])))
    ring = float(a["ring_pkts"] + a["fc_ring_pkts"]) / inj
    latency = lat["stack_us"] + (4.0 + 2.0 * inter + ring) \
        * lat["wire_hop_us"] + mean_wait
    if row["gating"]:
        rsw_on = float(a["rsw_powered"]) / (T * rsw_links)
        csw_on = float(a["csw_powered"]) / (T * csw_links)
        node_on = float(a["node_on"]) / (T * R * s["servers_per_rack"])
    else:
        rsw_on = csw_on = node_on = 1.0
    p_rsw = rsw_links * 2 * pw["sfp10"]
    p_csw = csw_links * 2 * pw["qsfp40"]
    dh = cfg["delay_hist"]
    wt = max(float(a["delay_wt"]), 1e-9)
    out = {
        "injected_pkts": float(a["injected"]),
        "delivered_pkts": float(a["csw_down_served"]),
        "dropped_pkts": float(a["drops"]),
        "drop_frac": float(a["drops"]) / inj,
        # the optics are perfect: no packet is lost to a fault
        "fault_dropped_pkts": 0.0,
        "in_flight_pkts": float(a["in_flight"]),
        "mean_latency_us": latency,
        "rsw_link_on_frac": rsw_on,
        "csw_link_on_frac": csw_on,
        "node_link_on_frac": node_on,
        "switch_energy_savings_frac":
            1.0 - (p_rsw * rsw_on + p_csw * csw_on) / (p_rsw + p_csw),
        "delay_mean_sampled_us": float(a["delay_sum"]) / wt,
        "delay_p99_us": _quantile(a["delay_hist"], 0.99, dh["min_us"],
                                  dh["bins"], dh["bins_per_octave"]),
    }
    if cfg.get("flows"):
        fh = cfg["flows"]["fct_hist"]
        n_done = max(float(a["flows_completed"]), 1e-9)
        out.update({
            "flows_started": float(a["flows_started"]),
            "flows_completed": float(a["flows_completed"]),
            "flows_evicted": float(a["flows_evicted"]),
            "flows_in_table": float(a["in_table"]),
            "fct_mean_us": float(a["fct_sum"]) / n_done,
            "fct_p99_us": _quantile(a["fct_hist"], 0.99, fh["min_us"],
                                    fh["bins"], fh["bins_per_octave"]),
        })
    return out


_REFS: dict = {}


def reference_metrics(cfg, rows, seeds, n_ticks, dtype=jnp.float32,
                      block=1000):
    """Metrics of each (row, seed) after ``n_ticks``, rows run together."""
    key = (json.dumps(cfg, sort_keys=True), jnp.dtype(dtype).name)
    if key not in _REFS:
        _REFS[key] = Ref(cfg, dtype)
    ref = _REFS[key]
    accs = ref.run(rows, seeds, n_ticks, block=block)
    return [metrics(cfg, r, a, n_ticks) for r, a in zip(rows, accs)]
