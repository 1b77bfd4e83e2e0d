"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lcdc_switch import switch_step
from repro.kernels.rwkv6_wkv import wkv_chunked
from repro.models.attention import chunked_attention

ATTN_CASES = [
    # (B, T, S, H, dh, causal, swa, dtype, blocks)
    (1, 64, 64, 1, 32, True, 0, jnp.float32, 32),
    (2, 128, 128, 2, 64, True, 0, jnp.float32, 64),
    (2, 128, 128, 2, 64, False, 0, jnp.float32, 64),
    (1, 128, 128, 2, 64, True, 32, jnp.float32, 32),
    (1, 128, 128, 1, 128, True, 0, jnp.bfloat16, 64),
    (1, 64, 64, 2, 80, False, 0, jnp.float32, 32),   # hubert head dim
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_naive(case):
    B, T, S, H, dh, causal, swa, dtype, blk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, dh)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, dh)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, dh)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, swa_window=swa,
                          block_q=blk, block_k=blk)
    expect = ref.attention_naive(q, k, v, causal=causal, swa_window=swa)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol,
                               rtol=tol)


def test_chunked_attention_is_also_a_valid_oracle():
    """The model's chunked attention (the CPU execution path) must agree
    with the naive softmax too."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 96, 2, 48))
    k = jax.random.normal(ks[1], (2, 96, 2, 48))
    v = jax.random.normal(ks[2], (2, 96, 2, 48))
    a = chunked_attention(q, k, v, causal=True, chunk_q=32, chunk_k=32)
    b = ref.attention_naive(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=2e-5)


WKV_CASES = [
    (1, 32, 1, 8, 16, jnp.float32),
    (2, 64, 3, 16, 16, jnp.float32),
    (2, 48, 2, 32, 16, jnp.float32),
    (1, 64, 2, 16, 8, jnp.float32),
    (1, 32, 2, 16, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("case", WKV_CASES)
def test_wkv_chunked_vs_sequential(case):
    B, T, H, dh, chunk, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    r = (jax.random.normal(ks[0], (B, T, H, dh)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, T, H, dh)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, dh)).astype(dtype)
    # realistic RWKV-6 decay range: w = exp(-exp(x))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, dh)) * 0.5)) \
        .astype(dtype)
    u = (jax.random.normal(ks[4], (H, dh)) * 0.3).astype(dtype)
    s0 = jax.random.normal(ks[5], (B, H, dh, dh)) * 0.1
    y1, sT1 = wkv_chunked(r, k, v, w, u, s0, chunk=chunk)
    y2, sT2 = ref.wkv_ref(r, k, v, w, u, s0)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2), atol=tol,
                               rtol=tol)


def _assert_switch_matches_ref(a, b):
    """Kernel outputs vs the oracle's, output by output. Every output
    is held to atol=1e-6 except occ_m2: a float32 sum of squared port
    backlogs in the hundreds, whose summation order differs between the
    kernel's lane reduction and the oracle's XLA reduce. Its rounding is
    a few ulp of the total (L terms of up to 0.5 ulp each, ~1.2e-7
    relative seen), so it gets rtol=1e-6 on top of the shared atol."""
    assert len(a) == len(b) == 8
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-6,
                                   rtol=1e-6 if i == 7 else 1e-7)


# odd S (16, 100) exercises the switch-axis padding: tiers need not be
# a multiple of the block (e.g. the 16-CSW tier under a 128 block)
@pytest.mark.parametrize("S,L,block", [(128, 4, 64), (256, 4, 128),
                                       (128, 8, 128), (16, 4, 128),
                                       (100, 4, 64)])
def test_switch_step_vs_ref(S, L, block):
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.uniform(ks[0], (S, L)) * 20
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    arr = jax.random.uniform(ks[2], (S,)) * 3
    a = switch_step(q, stage, arr, block_s=block, interpret=True)
    b = ref.switch_step_ref(q, stage, arr)
    _assert_switch_matches_ref(a, b)


# the extended datapath the simulator hot loop uses: K-component queues
# ([intra, inter] split), per-switch arrival vectors, draining top
# ports, multi-pkt serve rates, and non-default cap/watermarks
@pytest.mark.parametrize("S,L,K,serve_rate,block",
                         [(128, 4, 2, 1.0, 64), (16, 4, 2, 1.0, 128),
                          (64, 4, 1, 4.0, 32), (96, 8, 3, 2.0, 64)])
def test_switch_step_components_vs_ref(S, L, K, serve_rate, block):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.uniform(ks[0], (S, L, K)) * 15
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    arr = jax.random.uniform(ks[2], (S, K)) * 2
    drain = jax.random.bernoulli(ks[3], 0.4, (S,))
    kw = dict(cap=17.0, hi=0.6, lo=0.3, serve_rate=serve_rate)
    a = switch_step(q, stage, arr, drain, block_s=block, interpret=True,
                    **kw)
    b = ref.switch_step_ref(q, stage, arr, drain, **kw)
    _assert_switch_matches_ref(a, b)


@pytest.mark.parametrize("S,L,K,block", [(64, 4, 2, 32), (100, 3, 1, 128)])
def test_switch_step_valid_mask_vs_ref(S, L, K, block):
    """The multi-site padding mask: Pallas matches the ref oracle, and
    invalid switches are inert (queues pass through, nothing served,
    no triggers, no drops)."""
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.uniform(ks[0], (S, L, K)) * 15
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    valid = jax.random.bernoulli(ks[2], 0.6, (S,))
    # contract: invalid switches receive zero arrivals
    arr = jax.random.uniform(ks[3], (S, K)) * 2 * valid[:, None]
    a = switch_step(q, stage, arr, valid=valid, block_s=block,
                    interpret=True)
    b = ref.switch_step_ref(q, stage, arr, valid=valid)
    _assert_switch_matches_ref(a, b)
    nq, served, hi_t, lo_t, drop, wait, occ_m1, occ_m2 = b
    inv = ~np.asarray(valid)
    np.testing.assert_allclose(np.asarray(nq)[inv], np.asarray(q)[inv])
    assert np.all(np.asarray(served)[inv] == 0)
    assert np.all(np.asarray(hi_t)[inv] == 0)
    assert np.all(np.asarray(lo_t)[inv] == 0)
    assert np.all(np.asarray(drop)[inv] == 0)
    # the delay-histogram taps are inert on padded switches too
    assert np.all(np.asarray(wait)[inv] == 0)
    assert np.all(np.asarray(occ_m1)[inv] == 0)
    assert np.all(np.asarray(occ_m2)[inv] == 0)


@pytest.mark.parametrize("S,L,K,block", [(64, 4, 2, 32), (100, 4, 1, 128)])
def test_switch_step_per_link_valid_vs_ref(S, L, K, block):
    """The fault-injection axis: valid may be a PER-LINK (S, L) mask.
    Pallas matches the ref oracle; a dead port never serves and never
    receives the enqueue pick; a live switch whose ports are ALL dead
    counts its fed arrivals as drops (no silent loss)."""
    ks = jax.random.split(jax.random.PRNGKey(17), 4)
    q = jax.random.uniform(ks[0], (S, L, K)) * 15
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    link_valid = jax.random.bernoulli(ks[2], 0.55, (S, L))
    # force a few all-dead switches so the whole-switch-outage drop
    # accounting is actually exercised
    link_valid = link_valid.at[:4].set(False)
    arr = jax.random.uniform(ks[3], (S, K)) * 2
    a = switch_step(q, stage, arr, valid=link_valid, block_s=block,
                    interpret=True)
    b = ref.switch_step_ref(q, stage, arr, valid=link_valid)
    _assert_switch_matches_ref(a, b)
    nq, served, _, _, drop, _, _, _ = b
    dead = ~np.asarray(link_valid)
    # dead ports: untouched backlog, zero service
    np.testing.assert_allclose(np.asarray(jnp.sum(nq, 2))[dead],
                               np.asarray(jnp.sum(q, 2))[dead])
    assert np.all(np.asarray(jnp.sum(served, 2))[dead] == 0)
    # all-dead switches drop their entire arrival vector, exactly
    alldead = dead.all(axis=1)
    assert alldead[:4].all()
    np.testing.assert_allclose(
        np.asarray(drop)[alldead],
        np.asarray(jnp.sum(arr, 1))[alldead], atol=1e-6)


def test_switch_step_per_switch_cap_vs_ref():
    """cap may be a per-switch array; must survive the padded block."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    S, L = 100, 4
    q = jax.random.uniform(ks[0], (S, L)) * 20
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    arr = jax.random.uniform(ks[2], (S,)) * 3
    cap = jnp.linspace(10.0, 25.0, S)
    a = switch_step(q, stage, arr, cap=cap, block_s=128, interpret=True)
    b = ref.switch_step_ref(q, stage, arr, cap=cap)
    _assert_switch_matches_ref(a, b)


def test_switch_step_drain_blocks_enqueue_but_serves():
    """A draining top port must keep serving its backlog while new
    arrivals go to the remaining usable ports."""
    q = jnp.array([[5.0, 9.0]])[..., None]            # (1, 2, 1)
    stage = jnp.array([2], jnp.int32)
    arr = jnp.array([[3.0]])
    drain = jnp.array([True])
    nq, served, _, _, drop, wait, _, _ = ref.switch_step_ref(
        q, stage, arr, drain, cap=20.0)
    # arrival lands on port 0 (only usable), port 1 still drains 1 pkt
    np.testing.assert_allclose(np.asarray(nq[0, :, 0]), [7.0, 8.0])
    np.testing.assert_allclose(np.asarray(served[0, :, 0]), [1.0, 1.0])
    assert float(drop[0]) == 0.0
    # the arrival queues behind port 0's 5 existing pkts (not the
    # draining port's 9): backlog-age 5 ticks at serve_rate 1
    assert float(wait[0]) == 5.0


def test_switch_step_moment_taps_vs_direct():
    """The backlog-age / occupancy-moment outputs equal what a direct
    recomputation from the returned queues gives: enq_wait is the
    min-usable-port backlog over serve_rate, occ_m1/m2 are the first two
    moments of the post-serve per-port backlogs."""
    from repro.core import gating
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    S, L, K, rate = 64, 4, 2, 4.0
    q = jax.random.uniform(ks[0], (S, L, K)) * 15
    stage = jax.random.randint(ks[1], (S,), 1, L + 1)
    arr = jax.random.uniform(ks[2], (S, K)) * 2
    nq, served, _, _, _, wait, m1, m2 = ref.switch_step_ref(
        q, stage, arr, serve_rate=rate)
    usable = np.asarray(gating.usable_links(
        stage, jnp.zeros((S,), bool), L))
    qtot = np.asarray(jnp.sum(q, axis=2))
    mn = np.min(np.where(usable, qtot, np.inf), axis=1)
    np.testing.assert_allclose(np.asarray(wait), mn / rate, atol=1e-6)
    qpost = np.asarray(jnp.sum(nq, axis=2))
    np.testing.assert_allclose(np.asarray(m1), qpost.sum(1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m2), (qpost ** 2).sum(1),
                               atol=1e-4)


def test_wkv_kernel_plugs_into_model():
    """ops.model_kernel_fns routes the rwkv model through the Pallas wkv."""
    import dataclasses
    from repro.configs import get_config, reduced
    from repro.kernels.ops import model_kernel_fns
    from repro.models import model as M
    cfg = reduced(get_config("rwkv6-7b"))
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab),
             "targets": jax.random.randint(key, (2, 32), 0, cfg.vocab)}
    l_ref, _ = M.train_loss(cfg, params, batch)
    l_pal, _ = M.train_loss(cfg, params, batch,
                            kernel_fns=model_kernel_fns(use_pallas=True))
    assert abs(float(l_ref) - float(l_pal)) < 1e-3


def test_flash_kernel_plugs_into_model():
    from repro.configs import get_config, reduced
    from repro.kernels.ops import model_kernel_fns
    from repro.models import model as M
    import dataclasses
    cfg = dataclasses.replace(reduced(get_config("qwen3-8b")), attn_chunk=32)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 64), 0, cfg.vocab),
             "targets": jax.random.randint(key, (2, 64), 0, cfg.vocab)}
    l_ref, _ = M.train_loss(cfg, params, batch)
    l_pal, _ = M.train_loss(cfg, params, batch,
                            kernel_fns=model_kernel_fns(use_pallas=True))
    assert abs(float(l_ref) - float(l_pal)) < 1e-3
