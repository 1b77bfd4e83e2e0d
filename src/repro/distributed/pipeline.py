"""Optional pipeline parallelism: microbatched GPipe-style stage executor
built on shard_map + collective_permute.

The stage axis maps onto 'pod' (or any mesh axis): stage s holds layers
[s*L/S, (s+1)*L/S). Microbatches stream through; activations hop stages
with lax.ppermute. Bubble fraction = (S-1)/(M+S-1). This executor is
unit-tested at small scale (tests/test_pipeline_parallel.py) and offered
as a config choice; the default cell configs use FSDP+TP+EP which wins
at the assigned shapes (see DESIGN.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh, stage_axis: str, n_stages: int, layer_fn,
                   stacked_params, x, n_micro: int):
    """Run x (B, ...) through n_stages pipeline stages of layer_fn.

    stacked_params: pytree with leading dim == n_stages (one slice per
    stage). x is consumed microbatch-by-microbatch (B % n_micro == 0).
    Returns the final-stage output in original batch order.
    """
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro

    def stage_body(params_local, x_local):
        # params_local arrives with a size-1 leading shard dim: drop it
        params_local = jax.tree.map(lambda a: a[0], params_local)
        # x_local: (n_micro, mb, ...) all microbatches, this stage's copy
        s = jax.lax.axis_index(stage_axis)
        perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf, outs = carry           # buf: (mb, ...) in-flight act
            # stage 0 injects microbatch t (if in range)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(x_local, inject, 0,
                                                keepdims=False)
            cur = jnp.where(s == 0, x_in, buf)
            y = layer_fn(params_local, cur)
            # last stage banks its result at position t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            take = (s == n_stages - 1) & (t >= n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, jax.lax.dynamic_index_in_dim(
                    outs, out_idx, 0, keepdims=False)), out_idx, 0)
            # hop activations forward one stage
            buf = jax.lax.ppermute(y, stage_axis, perm_fwd)
            return (buf, outs), None

        buf0 = jnp.zeros_like(x_local[0])
        outs0 = jnp.zeros_like(x_local)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # only the last stage banked non-zero results; psum broadcasts them
        return jax.lax.psum(outs, stage_axis)

    xm = x.reshape(n_micro, mb, *x.shape[1:])
    out = jax.shard_map(
        stage_body, mesh=mesh,
        in_specs=(P(stage_axis), P()),      # params sharded by stage
        out_specs=P(),                      # every stage returns; last wins
        check_vma=False,
    )(stacked_params, xm)
    return out.reshape(B, *x.shape[1:])
