"""Attention: GQA (qk-norm / sliding-window / bidirectional) and MLA.

Memory discipline: full-sequence attention never materializes a (T, T)
score matrix — it scans over KV chunks with an online softmax (this is
also the pure-jnp oracle for the Pallas flash kernel; see
repro/kernels/ref.py which reuses `chunked_attention`).

Decode paths:
  * GQA: (B, 1) query against a (B, S, n_kv, dh) cache (rolling window for
    SWA archs).
  * MLA: absorbed-weight latent attention against a (B, S, kv_lora) +
    (B, S, rope) cache (DeepSeek-style; cache is ~(256+32) floats/token
    instead of n_heads * 128).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, init_dense, rms_norm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA parameters
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype):
    d, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_head
    ks = jax.random.split(key, 5)
    p = {
        "wq": init_dense(ks[0], d, H * dh, dtype),
        "wk": init_dense(ks[1], d, Hkv * dh, dtype),
        "wv": init_dense(ks[2], d, Hkv * dh, dtype),
        "wo": init_dense(ks[3], H * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def mla_init(key, cfg, dtype):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wdq": init_dense(ks[0], d, m.q_lora_rank, dtype),
        "wuq": init_dense(ks[1], m.q_lora_rank, H * qk_head, dtype),
        "wdkv": init_dense(ks[2], d, m.kv_lora_rank, dtype),
        "wkr": init_dense(ks[3], d, m.qk_rope_head_dim, dtype),
        "wuk": init_dense(ks[4], m.kv_lora_rank, H * m.qk_nope_head_dim, dtype),
        "wuv": init_dense(ks[5], m.kv_lora_rank, H * m.v_head_dim, dtype),
        "wo": init_dense(ks[6], H * m.v_head_dim, d, dtype),
        "q_ln": jnp.ones((m.q_lora_rank,), dtype),
        "kv_ln": jnp.ones((m.kv_lora_rank,), dtype),
    }


def attn_init(key, cfg, dtype):
    return mla_init(key, cfg, dtype) if cfg.attn_type == "mla" \
        else gqa_init(key, cfg, dtype)


# ---------------------------------------------------------------------------
# Chunked (online-softmax) attention over full sequences
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, causal=True, swa_window=0,
                      chunk_q=1024, chunk_k=1024):
    """q: (B,T,H,dq), k: (B,S,H,dq), v: (B,S,H,dv) -> (B,T,H,dv).

    Scans KV in chunks with a running (max, denom, acc) so peak memory is
    O(chunk_q * chunk_k) per head. Assumes T == S when causal.
    """
    B, T, H, dq = q.shape
    S, dv = k.shape[1], v.shape[-1]
    scale = dq ** -0.5
    cq, ck = min(chunk_q, T), min(chunk_k, S)
    nq, nk = T // cq, S // ck
    assert T % cq == 0 and S % ck == 0, (T, S, cq, ck)

    qc = q.reshape(B, nq, cq, H, dq)
    kc = k.reshape(B, nk, ck, H, dq)
    vc = v.reshape(B, nk, ck, H, dv)
    q_pos = jnp.arange(T).reshape(nq, cq)
    k_pos = jnp.arange(S).reshape(nk, ck)

    def q_step(_, qi):
        qb, qp = qi                                   # (B,cq,H,dq), (cq,)
        qb32 = qb.astype(jnp.float32) * scale

        def kv_step(carry, ki):
            m, l, acc = carry
            kb, vb, kp = ki
            s = jnp.einsum("bqhd,bkhd->bhqk", qb32, kb.astype(jnp.float32))
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if swa_window:
                mask &= qp[:, None] - kp[None, :] < swa_window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, H, cq), NEG_INF, jnp.float32),
                jnp.zeros((B, H, cq), jnp.float32),
                jnp.zeros((B, H, cq, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init,
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), k_pos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,H,cq,dv)
        return None, out.transpose(0, 2, 1, 3)           # (B,cq,H,dv)

    _, out = jax.lax.scan(q_step, None,
                          (qc.transpose(1, 0, 2, 3, 4), q_pos))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, dv)
    return out.astype(v.dtype)


def _repeat_kv(x, n_rep):
    if n_rep == 1:
        return x
    B, S, Hkv, dh = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (B, S, Hkv, n_rep, dh)).reshape(B, S, Hkv * n_rep, dh)


# ---------------------------------------------------------------------------
# GQA apply: full-sequence (train / prefill) and decode
# ---------------------------------------------------------------------------

def gqa_forward(p, cfg, x, *, positions, kernel_fn=None):
    """Full-sequence attention. x: (B,T,d). Returns (out, (k_cache, v_cache))."""
    B, T, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    k = (x @ p["wk"]).reshape(B, T, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, T, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kf, vf = _repeat_kv(k, H // Hkv), _repeat_kv(v, H // Hkv)
    if kernel_fn is not None:
        out = kernel_fn(q, kf, vf, causal=cfg.causal,
                        swa_window=cfg.swa_window)
    else:
        out = chunked_attention(q, kf, vf, causal=cfg.causal,
                                swa_window=cfg.swa_window,
                                chunk_q=cfg.attn_chunk, chunk_k=cfg.attn_chunk)
    return out.reshape(B, T, H * dh) @ p["wo"], (k, v)


def gqa_decode(p, cfg, x, cache, pos):
    """One-token decode. x: (B,1,d); cache: dict(k,v: (B,S,Hkv,dh)); pos: (B,).

    For SWA archs the cache is a rolling window of size cfg.swa_window and
    writes go to pos % window.
    """
    B = x.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    S = cache["k"].shape[1]
    q = (x @ p["wq"]).reshape(B, 1, H, dh)
    k = (x @ p["wk"]).reshape(B, 1, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, 1, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    # Scatter one row per stream (writes O(B*Hkv*dh) bytes, not the whole
    # cache; with donated caches XLA updates in place).
    write_idx = pos % S if cfg.swa_window else pos
    rows = jnp.arange(B)
    kc = cache["k"].at[rows, write_idx].set(k[:, 0])
    vc = cache["v"].at[rows, write_idx].set(v[:, 0])

    kf, vf = _repeat_kv(kc, H // Hkv), _repeat_kv(vc, H // Hkv)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32) * dh ** -0.5,
                   kf.astype(jnp.float32))
    idx = jnp.arange(S)
    valid = idx[None, :] <= pos[:, None]
    if cfg.swa_window:
        # rolling cache: once pos >= S-1 every slot holds a live in-window
        # entry; before that only slots 0..pos have been written.
        valid = valid | (pos[:, None] >= S - 1)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", prob, vf.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, H * dh)
    return out @ p["wo"], {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLA apply
# ---------------------------------------------------------------------------

def mla_forward(p, cfg, x, *, positions, kernel_fn=None):
    """Full-sequence MLA (naive/un-absorbed). Returns (out, latent cache)."""
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    cq = rms_norm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = rms_norm(x @ p["wdkv"], p["kv_ln"], cfg.norm_eps)   # (B,T,r_kv)
    k_rope = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                        cfg.rope_theta)                        # (B,T,1,dr)
    k_nope = (c_kv @ p["wuk"]).reshape(B, T, H, dn)
    v = (c_kv @ p["wuv"]).reshape(B, T, H, dv)

    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))],
                         axis=-1)
    if kernel_fn is not None:
        out = kernel_fn(qf, kf, v, causal=cfg.causal)
    else:
        out = chunked_attention(qf, kf, v, causal=cfg.causal,
                                chunk_q=cfg.attn_chunk, chunk_k=cfg.attn_chunk)
    out = out.reshape(B, T, H * dv) @ p["wo"]
    return out, (c_kv, k_rope[:, :, 0, :])


def mla_decode(p, cfg, x, cache, pos):
    """Absorbed-weight MLA decode: cache holds (c_kv, k_rope) only."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv, r = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                     m.v_head_dim, m.kv_lora_rank)
    S = cache["c_kv"].shape[1]

    cq = rms_norm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = (cq @ p["wuq"]).reshape(B, 1, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos[:, None], cfg.rope_theta)

    c_new = rms_norm(x @ p["wdkv"], p["kv_ln"], cfg.norm_eps)  # (B,1,r)
    kr_new = apply_rope((x @ p["wkr"])[:, :, None, :], pos[:, None],
                        cfg.rope_theta)[:, :, 0, :]            # (B,1,dr)

    rows = jnp.arange(B)
    c_kv = cache["c_kv"].at[rows, pos].set(c_new[:, 0])
    k_rope = cache["k_rope"].at[rows, pos].set(kr_new[:, 0])

    # Absorb W_uk into q: q_lat[b,h,r] = sum_n q_nope[b,h,n] * wuk[r, h*dn+n]
    wuk = p["wuk"].reshape(r, H, dn)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       wuk.astype(jnp.float32))
    scale = (dn + dr) ** -0.5
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv.astype(jnp.float32)) +
         jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                    k_rope.astype(jnp.float32))) * scale
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", prob, c_kv.astype(jnp.float32))
    wuv = p["wuv"].reshape(r, H, dv)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, wuv.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, H * dv)
    return out @ p["wo"], {"c_kv": c_kv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# Sequence-parallel decode (flash-decoding style, shard_map)
# ---------------------------------------------------------------------------

def gqa_decode_sp(p, cfg, x, cache, pos, dist):
    """One-token GQA decode with the KV cache sharded over (batch x seq).

    The plain GSPMD path scatters the new (k, v) row across the
    seq-sharded cache, which the partitioner can only realize by fully
    rematerializing (all-gathering) the cache every layer. Here the
    update and the attention run inside shard_map: each seq shard writes
    the new row iff `pos` lands in its range (a local masked write) and
    computes a partial (max, denom, weighted-value); the combine is one
    tiny psum per head. Per-layer collective volume drops from O(cache)
    to O(B*H*dh).
    """
    B = x.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    S = cache["k"].shape[1]
    da = dist.data_axes if len(dist.data_axes) > 1 else "data"
    ma = dist.model_axis
    m = dist.model_size

    q = (x @ p["wq"]).reshape(B, 1, H, dh)
    k = (x @ p["wk"]).reshape(B, 1, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, 1, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)

    from jax.sharding import PartitionSpec as P

    def local_attend(q, k_new, v_new, kc, vc, pos):
        # kc/vc: (B_loc, S_loc, Hkv, dh); this shard covers seq range
        # [j*S_loc, (j+1)*S_loc)
        S_loc = kc.shape[1]
        j = jax.lax.axis_index(ma)
        s0 = j * S_loc
        idx = jnp.arange(S_loc)[None, :]
        # masked local write of the new row
        local = (pos[:, None] >= s0) & (pos[:, None] < s0 + S_loc)
        li = jnp.clip(pos[:, None] - s0, 0, S_loc - 1)
        onrow = (idx == li) & local                    # (B_loc, S_loc)
        kc = jnp.where(onrow[..., None, None], k_new, kc)
        vc = jnp.where(onrow[..., None, None], v_new, vc)

        kf = _repeat_kv(kc, H // Hkv)
        vf = _repeat_kv(vc, H // Hkv)
        s = jnp.einsum("bqhd,bshd->bhqs",
                       q.astype(jnp.float32) * dh ** -0.5,
                       kf.astype(jnp.float32))
        valid = (s0 + idx) <= pos[:, None]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                    # (B,H,1)
        m_glob = jax.lax.pmax(m_loc, ma)
        e = jnp.exp(s - m_glob[..., None])
        l_loc = jnp.sum(e, axis=-1)
        acc = jnp.einsum("bhqs,bshd->bqhd", e, vf.astype(jnp.float32))
        l_glob = jax.lax.psum(l_loc, ma)
        acc = jax.lax.psum(acc, ma)
        out = acc / jnp.maximum(l_glob, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype), kc, vc

    out, kc, vc = jax.shard_map(
        local_attend, mesh=dist.mesh,
        in_specs=(P(da, None, None, None), P(da, None, None, None),
                  P(da, None, None, None), P(da, ma, None, None),
                  P(da, ma, None, None), P(da)),
        out_specs=(P(da, None, None, None), P(da, ma, None, None),
                   P(da, ma, None, None)),
        check_vma=False,
    )(q, k, v, cache["k"], cache["v"], pos)
    out = out.reshape(B, 1, H * dh) @ p["wo"]
    return out, {"k": kc, "v": vc}
