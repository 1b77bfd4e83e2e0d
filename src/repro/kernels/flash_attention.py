"""Flash attention as a Pallas TPU kernel.

Grid (B, H, nq, nk); the kv axis is the innermost ("arbitrary") dimension
so the (m, l, acc) online-softmax state lives in VMEM scratch across kv
steps. Q/K/V stream through VMEM in (block_q x d) / (block_k x d) tiles;
the (T, S) score matrix never exists. Causal/sliding-window blocks that
are fully masked are skipped with pl.when (real savings on TPU; the
interpret-mode oracle path executes them as no-ops).

Block sizes default to 128 to match the MXU (128x128) systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal: bool, swa_window: int, block_q: int, block_k: int,
            scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale   # (bq, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq,bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if swa_window:
            mask &= (qpos - kpos) < swa_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal
        pl.when(k_start <= q_start + block_q - 1)(compute)
    elif swa_window:
        pl.when(jnp.logical_and(k_start <= q_start + block_q - 1,
                                q_start - (k_start + block_k - 1)
                                < swa_window))(compute)
    else:
        compute()

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, swa_window=0,
                    block_q=128, block_k=128, interpret=True):
    """q: (B,T,H,dq), k/v: (B,S,H,dq)/(B,S,H,dv) -> (B,T,H,dv)."""
    B, T, H, dq = q.shape
    S, dv = k.shape[1], v.shape[-1]
    assert dq == v.shape[-1], "kernel assumes dq == dv (pad if MLA)"
    bq, bk = min(block_q, T), min(block_k, S)
    assert T % bq == 0 and S % bk == 0, (T, S, bq, bk)
    grid = (B, H, T // bq, S // bk)

    kern = functools.partial(
        _kernel, causal=causal, swa_window=swa_window,
        block_q=bq, block_k=bk, scale=dq ** -0.5)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, dq), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, dq), lambda b, h, i, j: (b, j, h, 0)),
            pl.BlockSpec((1, bk, 1, dv), lambda b, h, i, j: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dv),
                               lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denom
            pltpu.VMEM((bq, dv), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
