"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Two execution modes, selected by ``DistContext``:

* pure (single-device / smoke tests): global sort-based dispatch.
* distributed (inside the jitted step): ``shard_map`` over
  ('data', 'model') with one of two expert layouts:
    - ``ep``: experts sharded over the model axis (E % model == 0, e.g.
      Kimi 384/16, Jamba 16/16). Each (data, model)-device computes
      <its data-shard tokens> x <its experts>; the combine is a psum over
      'model'. Expert weights are FSDP-sharded over 'data' on the d_ff
      dim and explicitly all-gathered per layer (the FSDP all-gather is
      visible in the HLO, which the roofline/ICI-gating analyses read).
    - ``tp``: d_ff sharded over the model axis (E < model, e.g. Mixtral
      8e on a 16-way axis). All experts on every model shard, partial
      d_ff; combine is a psum over 'model'.

Token-choice top-k routing with softmax-renormalized gates, capacity
clamp (capacity_factor over the mean load) and a load-balancing aux loss.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import init_dense


@dataclass(frozen=True)
class DistContext:
    mesh: object                 # jax.sharding.Mesh | None
    data_axes: tuple = ("data",)  # ('pod','data') when multi-pod
    model_axis: str = "model"

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.mesh else 1

    @property
    def data_size(self) -> int:
        if not self.mesh:
            return 1
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n


def moe_init(key, cfg, dtype):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 4)
    return {
        "router": init_dense(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f)) * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d)) * f ** -0.5).astype(dtype),
    }


def ep_mode(cfg, dist: DistContext) -> str:
    if dist.model_size > 1 and cfg.expert_parallel and \
            cfg.n_experts % dist.model_size == 0:
        return "ep"
    return "tp"


def _top_k_gates(logits, k):
    """(S, E) fp32 -> (gates (S,k), idx (S,k), me (E,), ce (E,)).

    me/ce are the per-shard mean router prob / top-1 dispatch fraction;
    the Switch aux loss E*sum(me*ce) is formed AFTER averaging them
    globally (pmean over data) so distributed == single-device exactly.
    """
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    return gates, idx, me, ce


def _aux_loss(me, ce):
    return me.shape[-1] * jnp.sum(me * ce)


def _dispatch_compute_combine(x, gates, idx, w_gate, w_up, w_down,
                              e_lo, n_local, capacity):
    """Sort-based dispatch of (S,d) tokens to `n_local` experts
    [e_lo, e_lo+n_local), expert FFN, weighted combine. Static shapes.
    """
    S, d = x.shape
    K = idx.shape[1]
    flat_e = idx.reshape(-1)                        # (S*K,)
    flat_w = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(S), K)

    local = (flat_e >= e_lo) & (flat_e < e_lo + n_local)
    rel_e = jnp.where(local, flat_e - e_lo, n_local)  # overflow bucket
    order = jnp.argsort(rel_e, stable=True)
    sorted_e = rel_e[order]
    sorted_t = flat_t[order]
    sorted_w = flat_w[order]

    counts = jnp.zeros(n_local + 1, jnp.int32).at[sorted_e].add(1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(S * K, dtype=jnp.int32) - offsets[sorted_e]
    keep = (sorted_e < n_local) & (pos < capacity)

    slot = jnp.where(keep, sorted_e * capacity + pos, n_local * capacity)
    buf = jnp.zeros((n_local * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x[sorted_t], 0.0))
    buf = buf[:-1].reshape(n_local, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(-1, d)

    contrib = jnp.where(keep[:, None], y_buf[jnp.minimum(slot, len(y_buf) - 1)]
                        * sorted_w[:, None].astype(x.dtype), 0.0)
    return jnp.zeros((S, d), x.dtype).at[sorted_t].add(contrib)


def _capacity(cfg, n_tokens, n_experts):
    c = int(n_tokens * cfg.top_k / n_experts * cfg.capacity_factor) + 1
    return -(-c // 8) * 8  # round up to 8


def moe_apply_pure(p, cfg, x):
    """Single-device reference. x: (B,T,d)."""
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    gates, idx, me, ce = _top_k_gates(logits, cfg.top_k)
    cap = _capacity(cfg, B * T, cfg.n_experts)
    y = _dispatch_compute_combine(xf, gates, idx, p["w_gate"], p["w_up"],
                                  p["w_down"], 0, cfg.n_experts, cap)
    return y.reshape(B, T, d), _aux_loss(me, ce)


def moe_apply_dist(p, cfg, x, dist: DistContext):
    """Distributed MoE via shard_map. x: (B,T,d) sharded (data, None, None);
    when the batch doesn't divide the data axes (decode with B=1) tokens
    are replicated over data and only the model axis does real work."""
    B, T, d = x.shape
    mode = ep_mode(cfg, dist)
    m = dist.model_size
    da, ma = dist.data_axes, dist.model_axis
    b_shardable = B % dist.data_size == 0
    x_spec = P(da, None, None) if b_shardable else P(None, None, None)
    E, f = cfg.n_experts, cfg.d_expert
    # FSDP shards the expert d_ff dim over the (composite) data axes when
    # divisible, else over 'data' alone.
    fsdp_ax = da if f % dist.data_size == 0 else ("data",)
    if mode == "ep":
        e_spec = P(ma, None, fsdp_ax)
        e_spec_dn = P(ma, fsdp_ax, None)
    else:   # tp: d_ff over model, FSDP over data on the d_model dim
        e_spec = P(None, "data", ma)
        e_spec_dn = P(None, ma, "data")

    def local_moe(xl, router, wg, wu, wd):
        S_loc = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(S_loc, d)
        if mode == "ep":
            # FSDP all-gather of this model-shard's expert weights
            wg = jax.lax.all_gather(wg, fsdp_ax, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_ax, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_ax, axis=1, tiled=True)
            n_local, e_lo = E // m, jax.lax.axis_index(ma) * (E // m)
        else:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
            n_local, e_lo = E, 0
        logits = xf.astype(jnp.float32) @ router
        gates, idx, me, ce = _top_k_gates(logits, cfg.top_k)
        cap = _capacity(cfg, S_loc, E)
        y = _dispatch_compute_combine(xf, gates, idx, wg, wu, wd,
                                      e_lo, n_local, cap)
        if cfg.moe_combine == "psum_scatter" and d % m == 0:
            # combine straight into the d-sharded residual layout: half
            # the ring traffic of a full all-reduce, and the downstream
            # act_shard="dmodel" constraint needs exactly this shard.
            y = jax.lax.psum_scatter(y, ma, scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, ma)
        if b_shardable:
            me = jax.lax.pmean(me, da)
            ce = jax.lax.pmean(ce, da)
        return y.reshape(xl.shape[0], xl.shape[1], -1), \
            _aux_loss(me, ce)

    in_specs = (x_spec, P(), e_spec, e_spec, e_spec_dn)
    if cfg.moe_combine == "psum_scatter" and d % m == 0:
        y_spec = P(*(list(x_spec)[:2] + [ma]))
    else:
        y_spec = x_spec
    out_specs = (y_spec, P())
    y, aux = jax.shard_map(
        local_moe, mesh=dist.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux


def moe_apply(p, cfg, x, dist: DistContext | None = None):
    if dist is None or dist.mesh is None:
        return moe_apply_pure(p, cfg, x)
    return moe_apply_dist(p, cfg, x, dist)


def moe_param_specs(cfg, dist: DistContext) -> dict:
    """PartitionSpecs matching moe_apply_dist's in_specs."""
    mode = ep_mode(cfg, dist)
    ma = dist.model_axis
    fsdp_ax = dist.data_axes if cfg.d_expert % dist.data_size == 0 \
        else ("data",)
    if mode == "ep":
        return {
            "router": P(),
            "w_gate": P(ma, None, fsdp_ax),
            "w_up": P(ma, None, fsdp_ax),
            "w_down": P(ma, fsdp_ax, None),
        }
    return {
        "router": P(),
        "w_gate": P(None, "data", ma),
        "w_up": P(None, "data", ma),
        "w_down": P(None, ma, "data"),
    }
