"""Jitted dispatch wrappers for the Pallas kernels.

On TPU the Pallas body compiles natively (``interpret=False``); on
other backends the default is the pure-jnp reference path so jitted
code stays analyzable/compilable, with ``use_pallas=True`` running the
kernels in interpret mode (the correctness path exercised by
tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import lcdc_switch as _sw
from repro.kernels import rwkv6_wkv as _wkv
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, swa_window=0, use_pallas=None,
              block_q=128, block_k=128):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _fa.flash_attention(q, k, v, causal=causal,
                                   swa_window=swa_window, block_q=block_q,
                                   block_k=block_k,
                                   interpret=not _on_tpu())
    return _ref.attention_ref(q, k, v, causal=causal, swa_window=swa_window)


def wkv(r, k, v, w, u, state, *, use_pallas=None, chunk=16):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _wkv.wkv_chunked(r, k, v, w, u, state, chunk=chunk,
                                interpret=not _on_tpu())
    return _ref.wkv_ref(r, k, v, w, u, state)


def switch_step(queues, stage, arrivals, draining=None, *, valid=None,
                cap=20.0, hi=0.75, lo=0.22, serve_rate=1.0,
                use_pallas=None):
    """One LC/DC switch tick (the simulator's production datapath).

    Pallas on TPU, pure-jnp reference on CPU — identical semantics
    (tests/test_kernels.py pins the kernel to the oracle). See
    ref.switch_step_ref for the argument/return contract; queues may be
    (S, L, K) component-split or plain (S, L). ``valid`` is either the
    (S,) padding mask of heterogeneous-site batches (invalid switches
    are inert) or an (S, L) per-LINK usability mask — the
    fault-injection axis: a hard-faulted transceiver is a dead port on
    an otherwise live switch. Besides the datapath outputs, both paths
    emit the per-switch
    backlog-age (``enq_wait``: what an arrival queues behind, in ticks)
    and post-serve occupancy moments (``occ_m1``/``occ_m2``) that feed
    the simulator's in-scan delay histograms, so the distribution
    subsystem runs off the same oracle-checked kernel."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _sw.switch_step(queues, stage, arrivals, draining,
                               valid=valid, cap=cap, hi=hi, lo=lo,
                               serve_rate=serve_rate,
                               interpret=not _on_tpu())
    return _ref.switch_step_ref(queues, stage, arrivals, draining,
                                valid=valid, cap=cap, hi=hi, lo=lo,
                                serve_rate=serve_rate)


def model_kernel_fns(use_pallas: bool = True) -> dict:
    """kernel_fns dict for repro.models.model entry points."""
    return {
        "attention": functools.partial(attention, use_pallas=use_pallas),
        "wkv": lambda r, k, v, w, u, s: wkv(r, k, v, w, u, s,
                                            use_pallas=use_pallas),
    }
