"""RWKV-6 wkv recurrence as a chunked Pallas TPU kernel.

The sequential form (one (dh x dh) state update per token) starves the
MXU; the chunked form processes CHUNK tokens per grid step with two
matmuls plus rank-1 bookkeeping:

  within a chunk, with per-token log-decay lw_t = log(w_t) and inclusive
  cumsum L_t:
      r~_t = r_t * exp(L_{t-1})        (decay-adjusted queries)
      k~_s = k_s * exp(-L_s)           (decay-adjusted keys)
      scores = tril_strict(r~ @ k~^T) + diag((r*u*k).sum(-1))
      y = scores @ v + (r~ @ S)
      S' = exp(L_last) * S + (k~ * exp(L_last))^T @ v

  (exp(-L) stays in fp32 range because RWKV-6 decay w = exp(-exp(x))
  is bounded below ~exp(-e) per token and CHUNK = 16.)

Grid: (B, H, T / CHUNK) with the chunk axis sequential; the (dh x dh)
state lives in VMEM scratch across chunk steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


CHUNK = 16


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            s_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0, :].astype(jnp.float32)        # (ct, dh)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)              # (dh,)

    lw = jnp.log(jnp.maximum(w, 1e-38))              # (ct, dh) negative
    L = jnp.cumsum(lw, axis=0)                       # inclusive
    L_prev = L - lw                                  # exclusive
    r_t = r * jnp.exp(L_prev)
    k_t = k * jnp.exp(-L)

    S = s_ref[...]                                   # (dh, dh)
    y_inter = jax.lax.dot_general(r_t, S, (((1,), (0,)), ((), ())))

    scores = jax.lax.dot_general(r_t, k_t, (((1,), (1,)), ((), ())))
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(ti > si, scores, 0.0)         # strict lower
    diag = jnp.sum(r * u[None, :] * k, axis=1)       # bonus u on the diag
    y_intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ()))) \
        + diag[:, None] * v

    y_ref[0, :, 0, :] = (y_inter + y_intra).astype(y_ref.dtype)

    decay_all = jnp.exp(L[-1, :])                    # (dh,)
    kv = jax.lax.dot_general(k_t * decay_all[None, :], v,
                             (((0,), (0,)), ((), ())))
    s_ref[...] = decay_all[:, None] * S + kv

    @pl.when(ci == pl.num_programs(2) - 1)
    def _finish():
        sT_ref[0, 0] = s_ref[...]


def wkv_chunked(r, k, v, w, u, state, *, chunk=CHUNK, interpret=True):
    """r,k,v,w: (B,T,H,dh); u: (H,dh); state: (B,H,dh,dh) fp32.
    Returns (y (B,T,H,dh), final state)."""
    B, T, H, dh = r.shape
    ct = min(chunk, T)
    assert T % ct == 0, (T, ct)
    grid = (B, H, T // ct)
    kern = functools.partial(_kernel, chunk=ct)

    y, sT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ct, 1, dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, ct, 1, dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, ct, 1, dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, ct, 1, dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, dh), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ct, 1, dh), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, dh), r.dtype),
            jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, state.astype(jnp.float32))
    return y, sT
