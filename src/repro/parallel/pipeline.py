"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

The PP option of DESIGN.md section 5: layers are partitioned into S
stage groups sharded over a mesh axis; microbatches flow through a
collective-permute ring with a scan over S + M - 1 ticks (fill + steady
state + drain).  Stage handoff is one ppermute per tick — the TPU-native
point-to-point (the closest collective to an RDMA put, which is why it
lives here next to the BCL core).

Used by the training driver when a config requests pp_stages > 1 (the
mandated dry-run mesh exercises DP x TP x pod; PP composes with them on
a 4-axis mesh).  Correctness: tests/spmd_check.py proves a 4-stage
pipeline equals the sequential composition of the stages.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, stacked_params, x_microbatches, mesh: Mesh,
          axis: str = "stage"):
    """Run ``stage_fn`` as an S-stage pipeline.

    stage_fn(params_slice, x) -> y with x and y the same shape
    stacked_params: pytree with leading dim S (sharded over ``axis``)
    x_microbatches: (M, mb, ...) microbatches
    Returns (M, mb, ...) outputs of the final stage.
    """
    s = mesh.shape[axis]

    def per_stage(params_s, x_all):
        # params_s: this stage's slice (leading dim 1 from sharding)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_s)
        sid = jax.lax.axis_index(axis)
        m = x_all.shape[0]
        ticks = m + s - 1
        perm = [(i, (i + 1) % s) for i in range(s)]

        def step(buf, t):
            # stage 0 ingests microbatch t; later stages consume the ring
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(sid == 0, x_all[mb_idx], buf)
            y = stage_fn(params_local, x_in)
            nxt = jax.lax.ppermute(y, axis, perm)
            return nxt, y

        # the ring buffer varies over the stage axis from the first tick
        buf0 = jax.lax.pcast(jnp.zeros_like(x_all[0]), (axis,), to="varying")
        _, ys = jax.lax.scan(step, buf0, jnp.arange(ticks))
        # the final stage emits microbatch i at tick i + (s-1)
        out = jax.lax.dynamic_slice_in_dim(ys, s - 1, m, axis=0)
        out = jnp.where(sid == s - 1, out, 0)
        return jax.lax.psum(out, axis)      # broadcast the result

    nd = x_microbatches.ndim
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P(*([None] * nd))),
        out_specs=P(*([None] * nd)),
    )(stacked_params, x_microbatches)
