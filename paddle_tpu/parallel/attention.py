"""Sequence/context-parallel attention: ring attention and Ulysses.

The reference has no long-context parallelism (SURVEY §5.7) — its closest
artifact is the fused multihead-matmul inference pass
(``ir/multihead_matmul_fuse_pass.cc``). Here it is a first-class capability:

* **ring attention** — Q stays resident; K/V blocks rotate around the ``sp``
  ring via ``ppermute`` (one ICI hop per step) while a flash-style running
  (max, sum, out) accumulator folds each block in. Memory is O(S/sp) per
  chip and the ppermute overlaps with the block matmuls.
* **Ulysses** — ``all_to_all`` swaps the sharded dimension from sequence to
  heads, runs ordinary full-sequence attention on H/sp local heads, and
  swaps back. Two all-to-alls per layer, no per-block bookkeeping.

All shapes follow [B, S, H, D] (batch, sequence, heads, head_dim). The
per-shard kernels (`*_sharded`) are meant to run inside ``shard_map`` over
the ``sp`` axis with the sequence dimension sharded; the plain wrappers
set that up for callers holding global arrays.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import SP


def attention_reference(q, k, v, causal=False, scale=None):
    """Plain softmax attention on global [B, S, H, D] arrays (the numeric
    ground truth the parallel variants must match)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qi = jnp.arange(q.shape[1])[:, None]
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_scores(q, k, scale, causal, q_off, k_off):
    """Scores for a (local-Q, rotated-KV) block with global-position causal
    masking. q: [B, Sq, H, D], k: [B, Sk, H, D] -> [B, H, Sq, Sk].
    Accumulation happens in float32 regardless of input dtype (bf16 inputs
    would otherwise lose the softmax denominator over long rings)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qi = q_off + jnp.arange(q.shape[1])[:, None]
        ki = k_off + jnp.arange(k.shape[1])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    return s


def ring_attention_sharded(q, k, v, axis_name=SP, causal=False, scale=None):
    """Per-shard ring attention. q/k/v: [B, S/sp, H, D] local chunks laid out
    contiguously by rank along the ring. Runs inside shard_map."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    chunk = q.shape[1]
    q_off = rank * chunk

    b, _, h, d = q.shape
    m0 = jnp.full((b, h, chunk), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, chunk), jnp.float32)
    o0 = jnp.zeros((b, chunk, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def fold(acc, kb, vb, i):
        m, l, o = acc
        # source rank whose K/V block we currently hold: rotates backwards
        src = (rank - i) % n
        s = _block_scores(q, kb, scale, causal, q_off, src * chunk)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # fully-masked blocks (causal, future chunk): keep accumulators
        safe_m = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(jnp.isinf(s), 0.0, p)
        corr = jnp.where(jnp.isinf(m), jnp.where(jnp.isinf(m_new), 1.0, 0.0),
                         jnp.exp(m - safe_m))
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        return m_new, l, o

    def step(carry, i):
        m, l, o, kb, vb = carry
        m, l, o = fold((m, l, o), kb, vb, i)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (m, l, o, kb, vb), None

    # scan the first n-1 folds (each ends with a rotate); the last block is
    # folded outside the scan so no dead ppermute pair is emitted
    (m, l, o, kb, vb), _ = jax.lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(n - 1))
    m, l, o = fold((m, l, o), kb, vb, n - 1)
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ulysses_attention_sharded(q, k, v, axis_name=SP, causal=False,
                              scale=None):
    """Per-shard Ulysses attention. q/k/v: [B, S/sp, H, D]; requires
    H % sp == 0. all_to_all to [B, S, H/sp, D], full attention, swap back."""
    def seq_to_heads(x):
        # split heads (axis 2) across ranks, concat sequence (axis 1)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = attention_reference(qh, kh, vh, causal=causal, scale=scale)
    return heads_to_seq(oh)


def _wrap_sp(kernel, mesh, axis_name):
    spec = P(None, axis_name, None, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def ring_attention(q, k, v, mesh, axis_name=SP, causal=False, scale=None):
    """Global-array convenience wrapper: shards S over ``axis_name`` and runs
    the ring kernel under shard_map."""
    kern = functools.partial(ring_attention_sharded, axis_name=axis_name,
                             causal=causal, scale=scale)
    return _wrap_sp(kern, mesh, axis_name)(q, k, v)


def ulysses_attention(q, k, v, mesh, axis_name=SP, causal=False, scale=None):
    kern = functools.partial(ulysses_attention_sharded, axis_name=axis_name,
                             causal=causal, scale=scale)
    return _wrap_sp(kern, mesh, axis_name)(q, k, v)
