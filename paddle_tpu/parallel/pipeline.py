"""Pipeline parallelism over a ``pp`` mesh axis.

The reference's pipeline is the section-based trainer: the program is cut
into sections, each section runs in host threads and passes *scopes* through
bounded queues (``PipelineTrainer`` ``trainer.h:114``, ``SectionWorker``
``device_worker.h:290``, ``optimizer.py:3048``). TPU-native redesign: every
stage is one rank of the ``pp`` axis inside a single SPMD program;
activations hop stage→stage with ``ppermute`` (one ICI neighbor hop), the
GPipe fill/drain schedule is a ``lax.scan`` over M + P - 1 ticks, and the
backward schedule falls out of differentiating the scan — no threads, no
queues, one XLA program.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import PP


def pipeline_sharded(stage_fn, stage_params, microbatches, axis_name=PP):
    """GPipe schedule, per-shard (inside shard_map over ``axis_name``).

    stage_fn(params, x) -> y with y.shape == x.shape (uniform inter-stage
    activation shape, the usual pipeline contract).
    stage_params: THIS rank's stage parameters (any pytree).
    microbatches: [M, ...] microbatch inputs (replicated; only rank 0 reads).
    Returns [M, ...] outputs, valid on the last rank (zeros elsewhere).
    """
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    fwd = [(i, i + 1) for i in range(n - 1)]  # non-cyclic: rank0 recvs zeros

    out_buf = jnp.zeros((m,) + microbatches.shape[1:], microbatches.dtype)

    def tick(carry, t):
        recv, out_buf = carry
        mb = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        x = jnp.where(rank == 0, mb, recv)
        y = stage_fn(stage_params, x)
        # last stage finishes microbatch t-(n-1) at tick t
        oi = t - (n - 1)
        valid = (rank == n - 1) & (oi >= 0)
        cur = jax.lax.dynamic_index_in_dim(
            out_buf, jnp.clip(oi, 0, m - 1), axis=0, keepdims=False)
        out_buf = jax.lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(valid, y, cur), jnp.clip(oi, 0, m - 1), axis=0)
        recv = jax.lax.ppermute(y, axis_name, fwd)
        return (recv, out_buf), None

    recv0 = jnp.zeros_like(microbatches[0])
    (_, out_buf), _ = jax.lax.scan(
        tick, (recv0, out_buf), jnp.arange(m + n - 1))
    return out_buf


def pipeline(stage_fn, stacked_params, microbatches, mesh, axis_name=PP):
    """Global-array wrapper. ``stacked_params``: pytree whose leaves have a
    leading stage dimension of size pp (stage i's params at index i) — the
    analogue of the reference's per-section programs. ``microbatches``:
    [M, ...] global. Returns [M, ...] outputs, broadcast to all ranks (one
    psum from the last stage; callers needing the raw last-stage shard
    should use ``pipeline_sharded`` inside their own shard_map)."""

    def kernel(params, mbs):
        local = jax.tree_util.tree_map(lambda l: l[0], params)
        out = pipeline_sharded(stage_fn, local, mbs, axis_name)
        n = jax.lax.axis_size(axis_name)
        rank = jax.lax.axis_index(axis_name)
        return jax.lax.psum(
            jnp.where(rank == n - 1, out, jnp.zeros_like(out)), axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, microbatches)
