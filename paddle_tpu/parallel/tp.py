"""Tensor (model) parallelism primitives.

The reference shards only data, never weights (SURVEY §2.5 — TP "does not
exist" in the 2019 codebase); this is the gap-fill, Megatron-style but
expressed as per-shard SPMD kernels over a named ``tp`` mesh axis:

* column-parallel linear: W split on output dim; activations stay sharded
  (no collective) — pair with a row-parallel linear that psums.
* row-parallel linear: W split on input dim; partial products psummed over
  ICI.
* vocab-parallel embedding: table split on vocab dim; out-of-shard ids hit
  zero rows, psum merges.

Under jit+GSPMD the same layout falls out of sharding constraints; these
explicit kernels are for shard_map code paths (Fleet-collective mode) and
serve as the reference semantics.
"""

import functools

import jax
import jax.numpy as jnp

from .mesh import TP


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp_region(x, axis_name=TP):
    """Megatron's *f* operator: identity forward, psum backward. Place at
    the entry of a tensor-parallel block so replicated activations feeding
    tp-sharded weights get their cotangents summed across the tp ranks —
    after this, grads of params *outside* the block (layernorms, embeddings)
    are exact per-rank with no manual tp reductions."""
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    return (jax.lax.psum(g, axis_name),)


copy_to_tp_region.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp_region(x, axis_name=TP):
    """Megatron's *g* operator: psum forward, identity backward. Place at
    the exit of a tensor-parallel block (the row-parallel output reduce)."""
    return jax.lax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, g):
    return (g,)


reduce_from_tp_region.defvjp(_reduce_fwd, _reduce_bwd)


def pmean_exact(x, axis_name):
    """Mean over an axis with the mathematically exact VJP (cotangent/n).

    Under ``shard_map(..., check_vma=False)`` raw ``psum``/``pmean``
    transpose to another psum, scaling cotangents by the axis size; any
    loss reduction inside a differentiated per-shard program must use this
    (or ``reduce_from_tp_region``) instead."""
    return reduce_from_tp_region(x / jax.lax.axis_size(axis_name), axis_name)


def column_parallel_linear(x, w_local, b_local=None, axis_name=TP):
    """x: [.., D_in] replicated; w_local: [D_in, D_out/tp]. Returns sharded
    activations [.., D_out/tp] — no communication (axis_name is unused and
    kept only for call-site symmetry with row_parallel_linear)."""
    y = x @ w_local
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel_linear(x_local, w_local, b=None, axis_name=TP):
    """x_local: [.., D_in/tp] sharded; w_local: [D_in/tp, D_out]. psum over
    tp yields the full output on every rank; bias added once after."""
    y = jax.lax.psum(x_local @ w_local, axis_name)
    if b is not None:
        y = y + b
    return y


def vocab_parallel_embedding(ids, table_local, axis_name=TP):
    """ids: [..] int replicated; table_local: [V/tp, D] vocab shard. Each
    rank gathers its own rows (others zeroed) and psum merges."""
    vshard = table_local.shape[0]
    rank = jax.lax.axis_index(axis_name)
    lo = rank * vshard
    local_ids = ids - lo
    in_shard = (local_ids >= 0) & (local_ids < vshard)
    rows = jnp.take(table_local, jnp.clip(local_ids, 0, vshard - 1), axis=0)
    rows = jnp.where(in_shard[..., None], rows, 0.0)
    return jax.lax.psum(rows, axis_name)
