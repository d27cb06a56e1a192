"""Fused multi-head attention kernel (Pallas TPU).

Replaces the 5-op attention chain (matmul → +bias → softmax → dropout →
matmul) the reference computes as separate CUDA kernels (and its
``multihead_matmul_fuse_pass`` fuses for inference) with ONE kernel per
(batch, head): scores, softmax, dropout, and the PV matmul all stay in
VMEM, so the [S, S] probability tile never round-trips HBM. The backward
is a second single-block kernel that recomputes the probabilities
(flash-style: residuals are just q/k/v, not the S×S matrix) and emits
dq/dk/dv/dbias.

Dropout inside the kernel draws from the TPU PRNG
(``pltpu.prng_seed``/``prng_random_bits``) seeded per (batch, head); the
backward reseeds identically, so the regenerated mask is bit-exact.

Tier dispatch reads what the call shows: shapes, dtypes, dropout and
the platform. Tests reach a tier at a small shape by patching the
thresholds below (``_MAX_FUSED_SEQ``, ``_MAX_LONG_SEQ``,
``_FLASH_BLOCK_CANDIDATES``).

Training attention, single chip (``fused_attention`` -> ``_fused``):
  S <= 1024  — batch-blocked kernel, full [S, S] score tile in VMEM.
  1024 < S <= 4096 — Q-tiled long kernels (_fwd/_bwd_kernel_long): K/V
      for one (batch, head) live in VMEM (S·d stays small when S²
      doesn't), scores exist only as [Qb, S] tiles; dk/dv accumulate
      across the q-tile grid dim. Measured v5e BERT-base s=2048: 3.1x
      over the blockwise fallback (20k -> 63k tokens/sec), and +1.5%
      over the flash tier (r5 interleaved pairs: 64.3k vs 63.3k,
      spread ±0.4%), so the tier stays.
  S > 4096 — flash tier (_flash_*): BOTH q and k are tiled, so no
      VMEM term scales with S². The forward runs online
      softmax over k-tiles in VMEM scratch and saves per-row logsumexp;
      the backward is the flash-attention-2 SPLIT pair — one kernel
      accumulates dq over k-tiles, a second accumulates dk/dv over
      q-tiles — each regenerating probabilities from the saved
      logsumexp (its K/V + dK/dV [S, d] blocks plus [Qb, S] tiles
      overflow scoped VMEM at S=4096; see _long_qb). Row-broadcast
      bias only (per-row bias falls through to blockwise).
  fallback — blockwise online-softmax scan (no [S, S] anywhere).
  ``causal=True`` (every tier above and the fallback) — column > row is
      masked INSIDE the kernel from the tile's row and column indices
      (``_causal_mask``), so a causal call needs no [S, S] bias and keeps
      the tier its S selects: the flash tier takes row-broadcast bias
      only, and a ``[1, 1, S, S]`` causal bias would fall to the
      blockwise scan. The flash tier does only the causal work. Its grid
      walks the nt(nt+1)/2 tiles on and below the diagonal and no others
      (``_causal_steps``: two scalar-prefetched tables give a step's
      q-tile and k-tile), so a tile wholly above it costs neither a DMA
      nor a grid step. A tile below the diagonal is computed whole and
      unmasked. The diagonal tile is worked in strips (``_diag_strips``,
      edge ``_diag_edge``): a strip meets the blocks before its diagonal
      block, unmasked, and that block, masked; the blocks above the
      diagonal are neither computed nor masked. Such a block's
      probabilities were exp(-1e30 - m) = 0, so what changes is the order
      of the sums inside the diagonal tile, not what is summed.
      ``attn_flash_tiles_total{kind}`` counts a site's tiles computed and
      skipped, ``attn_causal_work_total{tier, kind}`` its grid steps,
      empty steps, scores computed and scores masked. The block and long
      tiers hold all of K a step and have no sweep to shorten; the causal
      mask covers their whole tile. v of ANOTHER WIDTH than q and k (dv !=
      d: latent attention's 192-wide queries and keys against 128-wide
      values) is the flash tier's at every S a tile divides, whatever S:
      its three kernels block v, o, do and dv at ``[Tb, dv]`` and q, k, dq
      and dk at ``[Tb, d]`` (``_flash_specs``), the PV, dV and dP products
      run at dv and the score and dq / dk products at d, so nothing is
      padded; the block and long tiers, which hold q, k and v under one
      block shape, are passed over, and where no tile divides S (or off
      the chip) the jnp forms below take two widths as they are. With dv =
      d every spec, kernel and jaxpr is what it was. A call without
      ``causal`` traces to the jaxpr it always did. Fewer KV than Q heads:
      the
      ``fused_multihead_attention`` op repeats K/V to the Q head count
      before the kernel (``num_kv_heads``).
  ``select`` given ([B, S, S] integer, nonzero = the query may see the
      key: a learned sparse attention's set of keys a query, one for all
      heads of a batch row, no gradient; ``sparse_index`` makes it) —
      select tier (_select_*) under ``causal``, whatever S a 128-row
      tile divides (tile 1024 / 512 / 256 / 128, the largest that
      divides S; no bias, no dropout) with at most 8 query heads a K/V
      head: the flash tier's online softmax and split backward with the
      selection's tile added to each score tile as 0 / -1e30, over the
      flash tier's causal tiles and strips. A grid step holds a K/V
      head's H / Hkv query heads: the selection's tile and the K/V tile
      are fetched once a step and serve them all, K/V stay at their own
      head count, dk/dv sum the group's heads inside. Elsewhere (not
      ``causal``, no tile divides S, a wider group, off the chip without
      the interpreter)
      the selection is a [B, 1, S, S] additive bias on the fallback
      below, which is the kernels' oracle. Without ``select`` nothing
      here is reached.

Decode (``attention_with_cache``): q [B, H, 1, d] against a KV ring
buffer [B, H, C, d].
  C >= 1024 — Pallas decode tier
      (_decode_fwd_kernel): online softmax over cache blocks with the
      per-sequence valid length in SMEM. Inference-only, no backward.
  fallback — masked-length one-pass reference (_ref_attention_cache).

Paged decode (``paged_attention_cache``): the KV cache is a SHARED pool
[P, H, ptok, d] indexed by per-slot page tables [B, npages]
(PagedAttention layout; ``paged_kv_cache_update`` is the block-granular
scatter).
  capacity >= 1024 — Pallas paged tier
      (_paged_decode_fwd_kernel): the decode kernel's online softmax
      with the page table + lengths as SMEM scalar-prefetch operands —
      each K/V block DMAs straight from the pool row the table names,
      so no dense [B, H, C, d] gather ever materializes.
  fallback — gather pages dense (``gather_paged_cache``), then the same
      masked-length reference: bit-identical to the dense ring path.

Sequence-parallel (``sequence_parallel_attention``): S sharded over a
mesh axis, selected per call by the op's ``strategy`` attribute
(``auto``: Ulysses when the axis divides the heads, else ring).
  ring — KV chunks rotate around ICI neighbors via ``lax.ppermute``
      inside ``shard_map``; each hop runs the flash forward
      (``_pallas_attention_flash``, when the chunk tiles) as the inner
      loop and merges per-hop (o, logsumexp) online; the custom-vjp
      backward is a second ring reusing the flash-attention-2 split
      kernels per hop. Causal hops with src > rank are skipped
      (~halves average work).
  ulysses — ``lax.all_to_all`` swaps heads<->sequence so each device
      runs FULL-sequence attention over H/n heads through the
      single-chip ``_fused`` dispatch above; auto-picked when the axis
      size divides H (ring is the general fallback).
"""

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret as _interpret
from .common import keep_across_recompute, named_pallas_call
from .common import supports_pallas as _supports_pallas

_MAX_FUSED_SEQ = 1024


KERNEL_TIERS = ("block", "block_bwd", "long", "long_bwd", "flash",
                "flash_bwd", "select", "decode", "paged")


# The name of every ``pl.pallas_call`` here, one a site: the device
# trace's instruction name of a kernel starts with it (``attn_block_fwd.3``),
# the same for a forward kernel traced as the primal or under ``jvp``.
KERNEL_NAMES = (
    "attn_block_fwd", "attn_block_bwd", "attn_long_fwd", "attn_long_bwd",
    "attn_flash_fwd", "attn_flash_bwd_dq", "attn_flash_bwd_dkv",
    "attn_select_fwd", "attn_select_bwd_dq", "attn_select_bwd_dkv",
    "attn_decode", "attn_paged")


def _kernel_call(name, kernel, **kw):
    """``common.named_pallas_call`` under one of ``KERNEL_NAMES``."""
    assert name in KERNEL_NAMES, name
    return named_pallas_call(name, kernel, **kw)


def _count_kernel(tier):
    """Trace-time record of which Pallas tier a dispatch took (one per
    traced ``pallas_call`` site, not per step) — how a caller proves the
    kernel, not the jnp fallback, is in its compiled program."""
    from ..fluid import monitor as _monitor

    assert tier in KERNEL_TIERS, tier
    _monitor.counter(
        "attn_kernel_dispatch_total",
        "Pallas attention kernel dispatches by tier (trace-time: one "
        "per traced program, not per step)", labels={"tier": tier}).inc()


def _count_flash_tiles(nt, causal):
    """Trace-time record, once a traced flash ``pallas_call`` site, of the
    score tiles a (batch, head) that the kernel computes and of those it
    skips (under ``causal``, the ones wholly above the diagonal)."""
    from ..fluid import monitor as _monitor

    computed = nt * (nt + 1) // 2 if causal else nt * nt
    for kind, n in (("computed", computed), ("skipped", nt * nt - computed)):
        _monitor.counter(
            "attn_flash_tiles_total",
            "flash attention score tiles a (batch, head), computed or "
            "skipped as wholly above the causal diagonal (trace-time: "
            "once a traced kernel site, not per step)",
            labels={"kind": kind}).inc(n)


def _uniform_from_bits(bits):
    """uint32 random bits -> uniform [0, 1) float32 (24-bit mantissa)."""
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * (1.0 / (1 << 24))


def _causal_mask(s, row0=0, col0=0):
    """``s`` with -1e30 wherever the column lies after the row, by index
    over the last two axes; ``row0`` / ``col0`` are the tile's offsets in
    the sequence. -1e30, not -inf (NaN discipline): column 0 is open to
    every row, so a row's maximum is real by the first k-tile (tile
    ``(i, 0)``, which no causal skip ever drops)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 2)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1)
    return jnp.where(cols <= rows, s, -1e30)


def _ref_attention(q, k, v, bias, scale, p_drop, seed, causal=False):
    """jnp reference (the fallback and the numerics oracle in tests)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = _causal_mask(s)
    p = jax.nn.softmax(s, axis=-1)
    if p_drop > 0.0:
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed[0])
        keep = jax.random.bernoulli(key, 1.0 - p_drop, p.shape)
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _blockwise_attention(q, k, v, bias, scale, p_drop, seed,
                         causal=False):
    """Online-softmax attention over K/V blocks (the single-device form of
    the ring-attention fold, ``parallel/attention.py``): the [S, S] score
    matrix never exists — per block, scores are folded into (max, denom,
    weighted-sum) carries. Used past the Pallas kernel's VMEM bound.
    The scan step is rematerialized, so backward memory is the per-step
    carries: O(nb · S · d) = O(S²·d/block) — block/d× below the score
    matrix (a flash-style custom vjp would tighten this to O(S·d)).

    Dropout semantics match the one-pass form: the softmax DENOMINATOR
    uses undropped weights; only the value accumulation is masked —
    identical to dropping normalized probabilities."""
    B, H, S, d = q.shape
    block = min(512, S)
    pad = (-S) % block  # prime/odd S: pad keys, mask pads, full-size blocks
    Sk = S + pad
    nb = Sk // block
    qf = q.astype(jnp.float32)
    bias_f = jnp.broadcast_to(bias.astype(jnp.float32),
                              (B, bias.shape[1], bias.shape[2], S))
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        bias_f = jnp.pad(bias_f, ((0, 0), (0, 0), (0, 0), (0, pad)),
                         constant_values=-1e30)
    kb = jnp.moveaxis(k.reshape(B, H, nb, block, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(B, H, nb, block, v.shape[-1]), 2, 0)
    bb = jnp.moveaxis(
        bias_f.reshape(B, bias_f.shape[1], bias_f.shape[2], nb, block),
        3, 0)

    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    o0 = jnp.zeros((B, H, S, v.shape[-1]), jnp.float32)

    def step(carry, xs):
        m, l, o = carry
        kblk, vblk, bblk, i = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       kblk.astype(jnp.float32)) * scale + bblk
        if causal:
            s = _causal_mask(s, 0, i * block)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        if p_drop > 0.0:
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), seed[0]), i)
            keep = jax.random.bernoulli(key, 1.0 - p_drop, p.shape)
            p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_new, l, o), None

    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, o0),
        (kb, vb, bb, jnp.arange(nb)))
    return (o / l[..., None]).astype(q.dtype)


def _fallback_attention(q, k, v, bias, scale, p_drop, seed, causal=False):
    """Off-kernel path: one-pass reference below the VMEM bound, blockwise
    online softmax above it."""
    if q.shape[2] > _MAX_FUSED_SEQ:
        return _blockwise_attention(q, k, v, bias, scale, p_drop, seed,
                                    causal)
    return _ref_attention(q, k, v, bias, scale, p_drop, seed, causal)


def _attn_block_fwd(q, k, v, bias_b, seed_ref, scale, p_drop, stream,
                    causal=False):
    """Shared per-(batch-block, head) forward math: q/k/v [Bb, S, d],
    bias_b [Bb, Sq|1, S] additive. Returns o [Bb, S, d] f32."""

    dn = (((2,), (2,)), ((0,), (0,)))            # batched q·kᵀ
    # matmuls in the input dtype (bf16 MXU under AMP), f32 accumulate
    s = jax.lax.dot_general(q, k, dn,
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_b
    if causal:
        s = _causal_mask(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    if p_drop > 0.0:
        pltpu.prng_seed(seed_ref[0] + stream)
        u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
        p = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
    return jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _attn_block_bwd(q, k, v, do, bias_b, seed_ref, scale, p_drop, stream,
                    causal=False):
    """Shared per-(batch-block, head) backward math (probabilities
    recomputed flash-style, dropout mask regenerated from the forward's
    stream). Returns (dq, dk, dv, ds) — ds [Bb, S, S] f32 pre-reduction
    for the bias gradient."""

    dn_qk = (((2,), (2,)), ((0,), (0,)))
    s = jax.lax.dot_general(q, k, dn_qk,
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_b
    if causal:
        s = _causal_mask(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)   # pre-dropout probs
    if p_drop > 0.0:
        pltpu.prng_seed(seed_ref[0] + stream)    # same stream as fwd
        u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
        keep = u >= p_drop
        pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    else:
        keep = None
        pd = p
    # dV = Pd^T dO ; dPd = dO V^T ; undo dropout ; softmax vjp ; dQ/dK
    lp = q.dtype  # matmul operand precision (bf16 under AMP, f32 accum)
    dv = jax.lax.dot_general(pd.astype(lp), do,
                             (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    dpd = jax.lax.dot_general(do, v, (((2,), (2,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)
    dp = dpd if keep is None else jnp.where(keep, dpd / (1.0 - p_drop), 0.0)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    ds_lp = ds.astype(lp)
    dq = jax.lax.dot_general(ds_lp, k, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    dk = jax.lax.dot_general(ds_lp, q, (((1,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    return dq, dk, dv, ds


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                scale, p_drop, n_heads, causal=False):
    """One grid step = a BLOCK of batches for one head: batched matmuls
    keep the MXU busy (a single (b, h) pair at S=128 is DMA-bound)."""

    b, h = pl.program_id(0), pl.program_id(1)
    o = _attn_block_fwd(q_ref[:, 0], k_ref[:, 0], v_ref[:, 0],
                        bias_ref[:, 0], seed_ref, scale, p_drop,
                        b * n_heads + h, causal)
    o_ref[:, 0] = o.astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dbias_ref, *, scale, p_drop,
                n_heads, acc_heads, reduce_rows, causal=False):
    b, h = pl.program_id(0), pl.program_id(1)
    dq, dk, dv, ds = _attn_block_bwd(
        q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], do_ref[:, 0],
        bias_ref[:, 0], seed_ref, scale, p_drop, b * n_heads + h, causal)
    dq_ref[:, 0] = dq.astype(dq_ref.dtype)
    dk_ref[:, 0] = dk.astype(dk_ref.dtype)
    dv_ref[:, 0] = dv.astype(dv_ref.dtype)
    # dbias reduced IN-kernel to the bias's broadcast shape: sum over the
    # query rows when bias rows broadcast, accumulate across the head
    # grid when bias heads broadcast (h is the fastest grid dim, so the
    # output block is revisited in order)
    contrib = ds
    if reduce_rows:
        contrib = jnp.sum(contrib, axis=1, keepdims=True)  # [Bb, 1, S]
    if acc_heads:
        @pl.when(pl.program_id(1) == 0)
        def _init():
            dbias_ref[:, 0] = contrib

        @pl.when(pl.program_id(1) != 0)
        def _acc():
            dbias_ref[:, 0] += contrib
    else:
        dbias_ref[:, 0] = contrib


_MAX_LONG_SEQ = 4096    # beyond this even Qb=64 tiles overflow scoped VMEM


def _long_qb(S, d):
    """Query-tile rows for the long kernels: largest of 128/64 whose bwd
    VMEM footprint stays inside the 16 MB scoped limit. Footprint =
    ~7.5 [Qb, S] f32 score-family tiles + double-buffered K/V (input
    blocks) and dK/dV (accumulating output blocks) [S, d]. Measured
    anchors: Qb=128 S=4096 -> 17.96 MB, Qb=64 S=4096 -> 16.92 MB (both
    over); Qb=128 S=2048 runs. The 13 MB acceptance bound keeps a
    safety margin under those measurements."""
    # Measured at S=4096/d=64: 17.96M (qb=128), 16.92M (64), 16.39M (32) —
    # the qb-independent K/V/dK/dV double-buffering dominates, so smaller
    # tiles can't rescue S=4096; the flash tier's split dq/dkdv pair
    # (_flash_dq_kernel/_flash_dkdv_kernel) takes over there.
    for qb in (128, 64):
        if S % qb:
            continue
        est = 7.5 * qb * S * 4 + 24 * S * d
        if est <= 13 * 1024 * 1024:
            return qb
    return None


def _fwd_kernel_long(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                     scale, p_drop, n_heads, n_qtiles, causal=False):
    """Long-sequence forward: grid (B, H, S/Qb). K/V for the whole
    (batch, head) sit in VMEM (S·d is small even when S² is not); each
    step computes one [Qb, S] score tile and its softmax in one pass —
    no online recurrence, no [S, S] materialization."""

    q = q_ref[0, 0]                               # [Qb, d]
    k = k_ref[0, 0]                               # [S, d]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0, 0]                        # [Qb|1, S]
    if causal:
        s = _causal_mask(s, pl.program_id(2) * q.shape[0])
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    if p_drop > 0.0:
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        pltpu.prng_seed(seed_ref[0] + (b * n_heads + h) * n_qtiles + i)
        u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
        p = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
    o_ref[0, 0] = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _bwd_kernel_long(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dbias_ref, *, scale, p_drop,
                     n_heads, n_qtiles, acc_heads, reduce_rows,
                     causal=False):
    """Long-sequence backward: q-tile is the fastest grid dim, so the
    (b, h)-indexed dk/dv blocks are revisited across tiles and accumulate
    in VMEM (same revisit-accumulate idiom as dbias in _bwd_kernel)."""

    q = q_ref[0, 0]                               # [Qb, d]
    k = k_ref[0, 0]                               # [S, d]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias_ref[0, 0]
    if causal:
        s = _causal_mask(s, pl.program_id(2) * q.shape[0])
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    if p_drop > 0.0:
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        pltpu.prng_seed(seed_ref[0] + (b * n_heads + h) * n_qtiles + i)
        u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
        keep = u >= p_drop
        pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    else:
        keep = None
        pd = p
    lp = q.dtype
    dv = jax.lax.dot_general(pd.astype(lp), do,
                             (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [S, d]
    dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [Qb, S]
    dp = dpd if keep is None else jnp.where(keep, dpd / (1.0 - p_drop), 0.0)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    ds_lp = ds.astype(lp)
    dq = jax.lax.dot_general(ds_lp, k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dk = jax.lax.dot_general(ds_lp, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init_kv():
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    @pl.when(i != 0)
    def _acc_kv():
        dk_ref[0, 0] += dk.astype(dk_ref.dtype)
        dv_ref[0, 0] += dv.astype(dv_ref.dtype)

    contrib = ds
    if reduce_rows:
        contrib = jnp.sum(contrib, axis=0, keepdims=True)  # [1, S]
        h = pl.program_id(1)
        first = (i == 0) if not acc_heads else \
            jnp.logical_and(h == 0, i == 0)

        @pl.when(first)
        def _init_b():
            dbias_ref[0, 0] = contrib

        @pl.when(jnp.logical_not(first))
        def _acc_b():
            dbias_ref[0, 0] += contrib
    else:
        # per-row bias: tile (b, h?, i) is visited once per head unless
        # heads broadcast, which accumulates across h
        if acc_heads:
            h = pl.program_id(1)

            @pl.when(h == 0)
            def _init_b2():
                dbias_ref[0, 0] = contrib

            @pl.when(h != 0)
            def _acc_b2():
                dbias_ref[0, 0] += contrib
        else:
            dbias_ref[0, 0] = contrib


def _long_specs(q, bias):
    B, H, S, d = q.shape
    QB = _long_qb(S, d)
    nq = S // QB
    grid = (B, H, nq)
    qspec = pl.BlockSpec((1, 1, QB, d), lambda b, h, i: (b, h, i, 0))
    kvspec = pl.BlockSpec((1, 1, S, d), lambda b, h, i: (b, h, 0, 0))
    hb, qb = bias.shape[1], bias.shape[2]
    bspec = pl.BlockSpec(
        (1, 1, QB if qb != 1 else 1, S),
        lambda b, h, i, _hb=hb, _qb=qb: (b, h if _hb > 1 else 0,
                                         i if _qb != 1 else 0, 0))
    return grid, qspec, kvspec, bspec, nq, QB


def _use_long_kernel(q, p_drop, bias):
    B, H, S, d = q.shape
    if not _supports_pallas():
        return False
    if not (_MAX_FUSED_SEQ < S <= _MAX_LONG_SEQ) or _long_qb(S, d) is None:
        return False
    if bias.shape[1] == 1 and bias.shape[2] != 1 and H > 1:
        # per-row head-broadcast bias (e.g. causal mask [B,1,S,S]): dbias
        # would need +=-accumulation across the NON-consecutive h grid dim
        # (i is fastest), which Pallas revisit-accumulate cannot do —
        # take the blockwise path instead
        return False
    return not (_interpret() and p_drop > 0.0)


def _pallas_attention_long(q, k, v, bias, scale, p_drop, seed,
                           causal=False):
    _count_kernel("long")
    B, H, S, d = q.shape
    grid, qspec, kvspec, bspec, nq, QB = _long_specs(q, bias)
    return _kernel_call(
        "attn_long_fwd",
        functools.partial(_fwd_kernel_long, scale=scale, p_drop=p_drop,
                          n_heads=H, n_qtiles=nq, causal=causal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kvspec, kvspec, bspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(seed, q, k, v, bias)


def _pallas_attention_long_bwd(q, k, v, bias, seed, do, scale, p_drop,
                               causal=False):
    _count_kernel("long_bwd")
    B, H, S, d = q.shape
    grid, qspec, kvspec, bspec, nq, QB = _long_specs(q, bias)
    acc_heads = bias.shape[1] == 1
    reduce_rows = bias.shape[2] == 1
    dbias_shape = (B, bias.shape[1], bias.shape[2], S)
    dbspec_blk = (1, 1, 1 if reduce_rows else QB, S)
    dbspec = pl.BlockSpec(
        dbspec_blk,
        lambda b, h, i, _ah=acc_heads, _rr=reduce_rows: (
            b, 0 if _ah else h, 0 if _rr else i, 0))
    f32 = jnp.float32
    dq, dk, dv, dbias = _kernel_call(
        "attn_long_bwd",
        functools.partial(_bwd_kernel_long, scale=scale, p_drop=p_drop,
                          n_heads=H, n_qtiles=nq, acc_heads=acc_heads,
                          reduce_rows=reduce_rows, causal=causal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kvspec, kvspec, bspec, qspec],
        out_specs=[qspec, kvspec, kvspec, dbspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(dbias_shape, f32)],
    )(seed, q, k, v, bias, do)
    return dq, dk.astype(q.dtype), dv.astype(q.dtype), dbias


# Largest first: measured v5e S=4096 fwd+bwd 18.1 ms (Tb=1024) vs
# 21.2 (512) / 40.2 (256) / 88.6 (128) — bigger score tiles amortize
# the k-sweep; Tb=1024 still fits scoped VMEM with the dropout PRNG
# tile live (22.2 ms measured with p=0.1).
_FLASH_BLOCK_CANDIDATES = (1024, 512, 256, 128)

# Scoped-VMEM ceiling for the flash kernels. Mosaic's default is 16 MB;
# at Tb=1024 with f32 operands and the dropout tile live the dk/dv
# kernel asks for 17.97 MB (v5e, S=8192, libtpu 0.0.34 — bf16 operands,
# and f32 at S=4096, stay under 16). The chip has 128 MiB of VMEM.
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=32 * 1024 * 1024)


def _flash_block(S):
    """Tile edge for the flash tier: largest candidate dividing S. Both q
    and k use the same edge, so the score tile is [Tb, Tb] and nothing in
    VMEM scales with S. At the preferred Tb=1024/d=64 each [Tb, Tb] f32
    tile is 4 MB — a handful fit the 16 MB scoped budget, and the
    measured kernels (incl. the dropout PRNG tile) run within it; adding
    live buffers to the flash kernel bodies eats that headroom fast."""
    for tb in _FLASH_BLOCK_CANDIDATES:
        if S % tb == 0:
            return tb
    return None


def _use_flash_kernel(q, p_drop, bias, v=None):
    """``v`` given with another width than q's (and k's): the block and
    long tiers hold q, k and v under one block shape and cannot serve, so
    the flash tier takes every S that a tile divides."""
    B, H, S, d = q.shape
    if not _supports_pallas():
        return False
    two = v is not None and v.shape[-1] != d
    if not two and (S <= _MAX_FUSED_SEQ
                    or _use_long_kernel(q, p_drop, bias)):
        return False        # the measured-faster long tier wins <=~3k
    if _flash_block(S) is None:
        return False
    if bias.shape[2] != 1:
        # per-row bias: dbias would need [B, H, S, S] f32 partials in
        # HBM (6+ GB at S=4096) — take the blockwise path instead
        return False
    return not (_interpret() and p_drop > 0.0)


def _flash_seed(seed0, b, h, i, j, n_heads, nq, nk):
    """One PRNG stream per (batch, head, q-tile, k-tile): all three flash
    kernels request [Tb, Tb]-shaped bits under this seed, so the dropout
    mask regenerates bit-exactly in both backward kernels."""
    return seed0 + (((b * n_heads + h) * nq + i) * nk + j)


def _flash_uniform(seed_ref, b, h, i, j, n_heads, nq, nk, shape):
    """Tile (i, j)'s dropout draw, ``shape`` = [Tb, Tb] uniforms. Every
    kernel draws a tile whole under its seed, and a part of the diagonal
    tile takes its slice of the draw, so all three drop the same pairs."""
    pltpu.prng_seed(_flash_seed(seed_ref[0], b, h, i, j, n_heads, nq, nk))
    return _uniform_from_bits(pltpu.prng_random_bits(shape))


def _dropped(u, p, p_drop):
    return jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)


def _drop_by(u, p_drop, *part):
    """``p -> p`` dropped by the tile's draw ``u`` (a strip's ``part``:
    its rows and columns of it), or None without dropout."""
    return None if u is None else lambda p: _dropped(u[part], p, p_drop)


# A causal sweep works its diagonal tile in strips of rows (of columns in
# dk/dv), so that the blocks wholly above the diagonal are not computed:
# at most ``_DIAG_STRIPS`` strips, each at least ``_DIAG_MIN_EDGE`` wide (a
# strip's lane offset is a whole number of 128-lane vregs). The forward
# cuts its tile only at d >= ``_DIAG_FWD_MIN_D``. Measured v5e, ms a call
# at Tb = 1024, the diagonal tile whole / in strips of 512 / 256 / 128
# (PR 41): dq 22.49 / 21.53 / 20.97 / 20.97 and dk/dv 28.23 / 27.05 /
# 26.57 / 28.93 at d = 64 (B 4, H 32, S 8192); dq 6.19 / 5.87 / 5.70 /
# 5.67 and dk/dv 8.24 / 7.82 / 7.75 / 7.62 at d = 256 (B 1, H 20); the
# forward 17.78 whole against 18.76 in strips of 256 at d = 64, 4.72
# against 4.56 at d = 256 (16.79 against 16.95 in the select tier at d =
# 128, 22.05 against 21.80 at 192 / 128).
_DIAG_STRIPS = 4
_DIAG_MIN_EDGE = 128
_DIAG_FWD_MIN_D = 192


def _diag_edge(tb, d, forward=False):
    """The strip edge of a ``tb``-row diagonal tile at head width ``d``;
    ``tb`` itself (one strip: the whole tile, masked) where none fits, and
    in the ``forward`` kernel below d = ``_DIAG_FWD_MIN_D``: there the
    strips' smaller matmuls and extra row reductions cost more than the
    blocks they leave out."""
    if forward and d < _DIAG_FWD_MIN_D:
        return tb
    for n in (_DIAG_STRIPS, 2):
        if tb % n == 0 and tb // n >= _DIAG_MIN_EDGE:
            return tb // n
    return tb


def _diag_strips(tb, e, rows):
    """The strips of a ``tb``-edge diagonal tile cut at edge ``e``, as
    ``(strip, parts)``: ``strip`` a slice of the tile's rows (``rows``) or
    columns, ``parts`` ``(slice, on the diagonal)`` of what it meets. A row
    strip meets the keys before its diagonal block and that block, a column
    strip that block and the queries after it; the blocks above the
    diagonal are in no strip. The backward kernels mask the diagonal block
    alone; the forward takes a row strip's keys as one part
    (``_flash_fwd_causal``)."""
    for o in range(0, tb, e):
        parts = ([(slice(0, o), False), (slice(o, o + e), True)] if rows
                 else [(slice(o, o + e), True), (slice(o + e, tb), False)])
        yield slice(o, o + e), [(p, diag) for p, diag in parts
                                if p.stop > p.start]


def _causal_steps(nt, by_row):
    """The (q-tile, k-tile) of each step of a causal sweep, as the two
    int32 tables that ``PrefetchScalarGridSpec`` hands to the kernel and to
    every index map: the tiles on and below the diagonal and no others,
    nt(nt + 1) / 2 a (batch, head). ``by_row`` (forward, dq): row by row,
    k-tile fastest, so that a row's carries and its dq block stay put and
    the row ends on its diagonal tile; else (dk/dv) k-tile by k-tile, each
    column from its diagonal tile down, so that dk and dv start there."""
    if by_row:
        steps = [(i, j) for i in range(nt) for j in range(i + 1)]
    else:
        steps = [(i, j) for j in range(nt) for i in range(j, nt)]
    return tuple(np.asarray(t, np.int32) for t in zip(*steps))


def _sweep(causal, B, H, nt, by_row=True, **specs):
    """``(pallas_call keywords, leading operands)`` of a flash or select
    sweep over ``(B, H)``: the grid ``(B, H, nt, nt)`` and no leading
    operand, or under ``causal`` the grid ``(B, H, steps)`` over
    ``_causal_steps`` and its two tables (numpy: a call built once serves
    many traces, and passes them in as constants each time)."""
    if not causal:
        return dict(grid=(B, H, nt, nt), **specs), ()
    steps = _causal_steps(nt, by_row)
    return dict(grid_spec=pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, H, len(steps[0])), **specs)), steps


def _count_causal_work(tier, nt, tb, edge, heads=1):
    """Trace-time record, once a traced causal kernel site of ``tier``
    (``flash`` or ``select``), of its work a (batch row, grid head: a
    head, or a select step's K/V group of ``heads`` query heads): grid
    steps, the steps among them with no tile, score elements computed, and
    those among them masked as above the diagonal."""
    from ..fluid import monitor as _monitor

    qt, kt = _causal_steps(nt, True)
    strips = tb // edge
    work = {"steps": len(qt),
            "empty_steps": int(np.sum(kt > qt)),
            "scores": heads * (nt * (nt - 1) // 2 * tb * tb
                               + nt * strips * (strips + 1) // 2
                               * edge * edge),
            "masked_scores": heads * nt * strips * edge * (edge - 1) // 2}
    for kind, n in work.items():
        _monitor.counter(
            "attn_causal_work_total",
            "causal attention kernel work a (batch row, head or K/V group): "
            "grid steps, steps with no tile, score elements computed and "
            "those masked above the diagonal (trace-time: once a traced "
            "kernel site, not per step)",
            labels={"tier": tier, "kind": kind}).inc(n)


def _total(xs):
    """The sum of ``xs`` from the left (one term: that term, no add)."""
    return functools.reduce(jnp.add, xs)


def _scores(q, k, scale):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * scale


def _online_softmax(s, v, m_ref, l_ref, acc_ref, idx, drop=None):
    """One online-softmax step for the rows ``ref[idx]`` of the carries:
    ``s`` the rows' scores against some keys, ``v`` those keys' values;
    ``drop(p)`` drops from the weights as the values see them (the
    denominator keeps them whole)."""
    m_prev = m_ref[idx]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[idx] = l_ref[idx] * corr + jnp.sum(p, axis=-1, keepdims=True)
    if drop is not None:
        p = drop(p)
    acc_ref[idx] = acc_ref[idx] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[idx] = m_new


def _ds(s, v, do, lse, dd, drop=None):
    """dS of (a part of) a tile from its scores: p = exp(s - L) from the
    saved logsumexp is exactly softmax without a second online pass;
    dS = P_dropped dP - P rowsum(dO O), dP = dO V^T."""
    p = jnp.exp(s - lse)
    pd = p if drop is None else drop(p)
    return _dscores(p, pd, do, v, dd)


def _dscores(p, pd, do, v, dd):
    dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return pd * dpd - p * dd


def _dkdv(s, q, v, do, lse, dd, drop=None):
    """``(dK unscaled, dV)`` of (a part of) a tile from its scores."""
    p = jnp.exp(s - lse)
    pd = p if drop is None else drop(p)
    lp = q.dtype
    dv = jax.lax.dot_general(pd.astype(lp), do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = _dscores(p, pd, do, v, dd)
    return jax.lax.dot_general(ds.astype(lp), q, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32), dv


def _dq_of(ds, k):
    """dS K, unscaled."""
    return jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_emit(o_ref, lse_ref, acc_scr, m_scr, l_scr):
    l = l_scr[...]
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m_scr[...] + jnp.log(l)           # [Tb, 1]


def _flash_init(acc_scr, m_scr, l_scr):
    m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                      lse_ref, acc_scr, m_scr, l_scr, *, scale, p_drop,
                      n_heads, nq, nk):
    """Grid (B, H, nq, nk), k-tile fastest: classic online softmax. The
    (m, l, acc) carries live in VMEM scratch across the k-tile sweep; o
    and the row logsumexp L are written on the last k-tile. Dropout
    masks only the value accumulation — the denominator uses undropped
    weights (same semantics as _blockwise_attention). Under ``causal``:
    ``_flash_fwd_causal``."""
    j = pl.program_id(3)
    q = q_ref[0, 0]                                   # [Tb, d]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = _scores(q, k, scale)
    s = s + bias_ref[0, 0]                        # [1, Tb] row-broadcast
    pl.when(j == 0)(lambda: _flash_init(acc_scr, m_scr, l_scr))

    def drop(p):
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        return _dropped(_flash_uniform(seed_ref, b, h, i, j, n_heads, nq,
                                       nk, p.shape), p, p_drop)

    _online_softmax(s, v, m_scr, l_scr, acc_scr, ...,
                    drop if p_drop > 0.0 else None)
    pl.when(j == nk - 1)(
        lambda: _flash_emit(o_ref, lse_ref, acc_scr, m_scr, l_scr))


def _flash_fwd_causal(qt_ref, kt_ref, seed_ref, q_ref, k_ref, v_ref,
                      bias_ref, o_ref, lse_ref, acc_scr, m_scr, l_scr, *,
                      scale, p_drop, n_heads, nt, edge):
    """Grid (B, H, nt(nt + 1) / 2) over the causal tiles row by row
    (``_causal_steps``): a tile below the diagonal is one unmasked online
    softmax step; the diagonal tile, last of its row, one step a row strip
    of ``edge`` against the keys up to its diagonal block's end, masked by
    index (as one part: two cost a second pass of row reductions, more
    than the mask on the blocks before the diagonal one); then o and L
    are written."""
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = q_ref.shape[2]

    u = (_flash_uniform(seed_ref, b, h, i, j, n_heads, nt, nt, (tb, tb))
         if p_drop > 0.0 else None)

    pl.when(j == 0)(lambda: _flash_init(acc_scr, m_scr, l_scr))

    @pl.when(j < i)
    def _below():
        s = _scores(q_ref[0, 0], k_ref[0, 0], scale) + bias_ref[0, 0]
        _online_softmax(s, v_ref[0, 0], m_scr, l_scr, acc_scr, ...,
                        _drop_by(u, p_drop))

    @pl.when(j == i)
    def _diagonal():
        for o in range(0, tb, edge):
            rows, keys = slice(o, o + edge), slice(0, o + edge)
            s = (_scores(q_ref[0, 0, rows], k_ref[0, 0, keys], scale)
                 + bias_ref[0, 0, :, keys])
            _online_softmax(_causal_mask(s, o), v_ref[0, 0, keys], m_scr,
                            l_scr, acc_scr, rows,
                            _drop_by(u, p_drop, rows, keys))
        _flash_emit(o_ref, lse_ref, acc_scr, m_scr, l_scr)


def _flash_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     lse_ref, dd_ref, dq_ref, dbias_ref, *, scale, p_drop,
                     n_heads, nq, nk):
    """Split backward, half 1 — grid (B, H, nq, nk), k-tile fastest: the
    dq block (keyed on the q-tile) accumulates over consecutive k-tile
    steps. Probabilities regenerate from the saved logsumexp (``_ds``).
    Also emits per-(q-tile, k-tile) dbias partials, reduced outside the
    kernel. Under ``causal``: ``_flash_dq_causal``."""
    j = pl.program_id(3)
    q = q_ref[0, 0]                                   # [Tb, d]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]                               # [Tb, 1]
    dd = dd_ref[0, 0]                                 # rowsum(do*o) [Tb, 1]
    s = _scores(q, k, scale)
    s = s + bias_ref[0, 0]

    def drop(p):
        b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        return _dropped(_flash_uniform(seed_ref, b, h, i, j, n_heads, nq,
                                       nk, p.shape), p, p_drop)

    ds = _ds(s, v, do, lse, dd, drop if p_drop > 0.0 else None)
    dbias_ref[0, 0] = jnp.sum(ds, axis=0, keepdims=True)
    contrib = _dq_of(ds, k) * scale

    @pl.when(j == 0)
    def _init():
        dq_ref[0, 0] = contrib

    @pl.when(j != 0)
    def _acc():
        dq_ref[0, 0] += contrib


def _flash_dq_causal(qt_ref, kt_ref, seed_ref, q_ref, k_ref, v_ref,
                     bias_ref, do_ref, lse_ref, dd_ref, dq_ref, dbias_ref, *,
                     scale, p_drop, n_heads, nt, edge):
    """Grid (B, H, nt(nt + 1) / 2) over the causal tiles row by row: dq
    starts at 0 on a row's first tile and gathers every tile's part; the
    diagonal tile's is gathered strip by strip, and so is its dbias
    partial, column block by column block."""
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = q_ref.shape[2]

    u = (_flash_uniform(seed_ref, b, h, i, j, n_heads, nt, nt, (tb, tb))
         if p_drop > 0.0 else None)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

    @pl.when(j < i)
    def _below():
        k = k_ref[0, 0]
        s = _scores(q_ref[0, 0], k, scale) + bias_ref[0, 0]
        ds = _ds(s, v_ref[0, 0], do_ref[0, 0], lse_ref[0, 0], dd_ref[0, 0],
                 _drop_by(u, p_drop))
        dbias_ref[0, 0] = jnp.sum(ds, axis=0, keepdims=True)
        dq_ref[0, 0] += _dq_of(ds, k) * scale

    @pl.when(j == i)
    def _diagonal():
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, dbias_ref.dtype)
        for rows, parts in _diag_strips(tb, edge, rows=True):
            q, do = q_ref[0, 0, rows], do_ref[0, 0, rows]
            lse, dd = lse_ref[0, 0, rows], dd_ref[0, 0, rows]
            dqs = []
            for cols, diag in parts:
                k = k_ref[0, 0, cols]
                s = _scores(q, k, scale) + bias_ref[0, 0, :, cols]
                ds = _ds(_causal_mask(s) if diag else s, v_ref[0, 0, cols],
                         do, lse, dd, _drop_by(u, p_drop, rows, cols))
                dbias_ref[0, 0, :, cols] += jnp.sum(ds, axis=0,
                                                    keepdims=True)
                dqs.append(_dq_of(ds, k))
            dq_ref[0, 0, rows] += _total(dqs) * scale


def _flash_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                       lse_ref, dd_ref, dk_ref, dv_ref, *, scale, p_drop,
                       n_heads, nq, nk):
    """Split backward, half 2 — grid (B, H, nk, nq), q-tile fastest: the
    dk/dv blocks (keyed on the k-tile) accumulate over consecutive
    q-tile steps. The PRNG seed uses the same (i, j) formula as the
    forward, so the regenerated mask is bit-exact despite the
    transposed grid order. Under ``causal``: ``_flash_dkdv_causal``."""
    j, i = pl.program_id(2), pl.program_id(3)
    q = q_ref[0, 0]                                   # [Tb, d]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]
    dd = dd_ref[0, 0]
    s = _scores(q, k, scale)
    s = s + bias_ref[0, 0]

    def drop(p):
        b, h = pl.program_id(0), pl.program_id(1)
        return _dropped(_flash_uniform(seed_ref, b, h, i, j, n_heads, nq,
                                       nk, p.shape), p, p_drop)

    dk, dv = _dkdv(s, q, v, do, lse, dd, drop if p_drop > 0.0 else None)
    dk = dk * scale

    @pl.when(i == 0)
    def _init():
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv

    @pl.when(i != 0)
    def _acc():
        dk_ref[0, 0] += dk
        dv_ref[0, 0] += dv


def _flash_dkdv_causal(qt_ref, kt_ref, seed_ref, q_ref, k_ref, v_ref,
                       bias_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref, *,
                       scale, p_drop, n_heads, nt, edge):
    """Grid (B, H, nt(nt + 1) / 2) over the causal tiles k-tile by k-tile,
    each column from its diagonal tile down: the diagonal tile, worked in
    column strips (``_diag_strips``), writes dk and dv; the tiles below
    it add to them."""
    b, h, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = q_ref.shape[2]

    u = (_flash_uniform(seed_ref, b, h, i, j, n_heads, nt, nt, (tb, tb))
         if p_drop > 0.0 else None)

    @pl.when(j < i)
    def _below():
        q = q_ref[0, 0]
        s = _scores(q, k_ref[0, 0], scale) + bias_ref[0, 0]
        dk, dv = _dkdv(s, q, v_ref[0, 0], do_ref[0, 0], lse_ref[0, 0],
                       dd_ref[0, 0], _drop_by(u, p_drop))
        dk_ref[0, 0] += dk * scale
        dv_ref[0, 0] += dv

    @pl.when(j == i)
    def _diagonal():
        for cols, parts in _diag_strips(tb, edge, rows=False):
            k, v = k_ref[0, 0, cols], v_ref[0, 0, cols]
            bias = bias_ref[0, 0, :, cols]
            dks, dvs = [], []
            for rows, diag in parts:
                q = q_ref[0, 0, rows]
                s = _scores(q, k, scale) + bias
                dk, dv = _dkdv(_causal_mask(s) if diag else s, q, v,
                               do_ref[0, 0, rows], lse_ref[0, 0, rows],
                               dd_ref[0, 0, rows],
                               _drop_by(u, p_drop, rows, cols))
                dks.append(dk)
                dvs.append(dv)
            dk_ref[0, 0, cols] = _total(dks) * scale
            dv_ref[0, 0, cols] = _total(dvs)


def _flash_specs(q, bias, causal, kfast=True, dv=None):
    """Block specs of the flash kernels' grid ``(B, H, slow, fast)``; the
    last two are the q-tile's and the k-tile's at v's width ``dv`` (o and
    do; v and dv), which are ``qspec`` and ``kspec`` again where v is as
    wide as q and k:
    ``kfast`` has the k-tile fastest (forward, dq), else the q-tile (dk/dv).
    Under ``causal`` the grid is ``(B, H, step)`` and a step's tiles are
    the tables' (``_causal_steps``), whatever their order: one set of
    specs serves the three kernels."""
    B, H, S, d = q.shape
    TB = _flash_block(S)
    nt = S // TB
    hb = bias.shape[1]

    def tiles(*g):      # grid indices -> (b, h, q-tile, k-tile) to fetch
        return g if kfast else (g[0], g[1], g[3], g[2])

    def spec(block, index):
        if causal:
            return pl.BlockSpec(block, lambda b, h, t, qt, kt: index(
                b, h, qt[t], kt[t]))
        return pl.BlockSpec(block, lambda *g: index(*tiles(*g)))

    qspec = spec((1, 1, TB, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = spec((1, 1, TB, d), lambda b, h, i, j: (b, h, j, 0))
    bspec = spec((1, 1, 1, TB),
                 lambda b, h, i, j: (b, h if hb > 1 else 0, 0, j))
    # per-row stats (lse, rowsum(do*o)) ride as [B, H, S, 1]: trailing
    # dim 1 satisfies the TPU block-shape rule (equal to the array dim)
    # and [Tb, 1] blocks line up with the kernels' column-vector math
    rowspec = spec((1, 1, TB, 1), lambda b, h, i, j: (b, h, i, 0))
    # dbias partials: one [1, TB] row-sum per (q-tile, k-tile), each
    # block written at most once (no cross-grid-dim revisit hazards);
    # laid out [B, H*nt, 1, S] to satisfy the TPU block-shape rule
    dbpspec = spec((1, 1, 1, TB),
                   lambda b, h, i, j: (b, h * nt + i, 0, j))
    dv = d if dv is None else dv
    ospec = spec((1, 1, TB, dv), lambda b, h, i, j: (b, h, i, 0))
    vspec = spec((1, 1, TB, dv), lambda b, h, i, j: (b, h, j, 0))
    return TB, nt, qspec, kspec, bspec, rowspec, dbpspec, ospec, vspec


def _flash_kernel(plain, causal_kernel, causal, nt, edge, **kw):
    """``plain`` over the grid ``(B, H, nt, nt)``, or ``causal_kernel``
    over the causal steps, given ``kw``."""
    if causal:
        return functools.partial(causal_kernel, nt=nt, edge=edge, **kw)
    return functools.partial(plain, nq=nt, nk=nt, **kw)


@functools.lru_cache(maxsize=None)
def _flash_calls(shape, dtype, bias_heads, dv, scale, p_drop, causal, tb,
                 edges, interpreted):
    """The flash tier's three ``pallas_call``s of one signature - q's
    ``shape`` and ``dtype``, the bias's head count, v's width ``dv`` - as
    ``{kernel: (call, its leading operands)}``, built ONCE: a
    ``pallas_call`` caches its trace on itself, so a step's layers, the
    replay and the lowering walk each kernel body once (as
    ``delta_rule._calls``). ``tb`` and ``edges`` (the diagonal strips'
    edges, forward and backward, under ``causal``) follow from the shape,
    and ``interpreted`` (``common.interpret()``) is read as the calls are
    built: all three are part of the key only."""
    B, H, S, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype)
    bias = jax.ShapeDtypeStruct((B, bias_heads, 1, S), jnp.float32)
    TB, nt, qspec, kspec, bspec, rowspec, dbpspec, ospec, vspec = \
        _flash_specs(q, bias, causal, dv=dv)
    assert TB == tb, (TB, tb)
    f32 = jnp.float32
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def sweep(plain, causal_kernel, edge, out_shape, by_row=True, **specs):
        """A sweep's kernel, ``pallas_call`` keywords and leading
        operands."""
        kw, steps = _sweep(causal, B, H, nt, by_row, **specs)
        return _flash_kernel(plain, causal_kernel, causal, nt, edge,
                             scale=scale, p_drop=p_drop, n_heads=H), dict(
            kw, out_shape=out_shape,
            compiler_params=_FLASH_COMPILER_PARAMS), steps

    kernel, kw, steps = sweep(
        _flash_fwd_kernel, _flash_fwd_causal, edges[0],
        [jax.ShapeDtypeStruct((B, H, S, dv), dtype),
         jax.ShapeDtypeStruct((B, H, S, 1), f32)],
        in_specs=[smem, qspec, kspec, vspec, bspec],
        out_specs=[ospec, rowspec],
        scratch_shapes=[pltpu.VMEM((TB, dv), f32), pltpu.VMEM((TB, 1), f32),
                        pltpu.VMEM((TB, 1), f32)])
    calls = {"fwd": (_kernel_call("attn_flash_fwd", kernel, **kw), steps)}
    kernel, kw, steps = sweep(
        _flash_dq_kernel, _flash_dq_causal, edges[1],
        [jax.ShapeDtypeStruct(shape, f32),
         jax.ShapeDtypeStruct((B, H * nt, 1, S), f32)],
        in_specs=[smem, qspec, kspec, vspec, bspec, ospec, rowspec, rowspec],
        out_specs=[qspec, dbpspec])
    calls["dq"] = (_kernel_call("attn_flash_bwd_dq", kernel, **kw), steps)
    # the k-tile is the SLOW tile dim (transposed grid; under causal the
    # tables' order) so dk/dv accumulate over consecutive q-tile steps
    if not causal:
        _, _, qspec, kspec, bspec, rowspec, _, ospec, vspec = _flash_specs(
            q, bias, causal, kfast=False, dv=dv)
    kernel, kw, steps = sweep(
        _flash_dkdv_kernel, _flash_dkdv_causal, edges[1],
        [jax.ShapeDtypeStruct(shape, f32),
         jax.ShapeDtypeStruct((B, H, S, dv), f32)], by_row=False,
        in_specs=[smem, qspec, kspec, vspec, bspec, ospec, rowspec, rowspec],
        out_specs=[kspec, vspec])
    calls["dkv"] = (_kernel_call("attn_flash_bwd_dkv", kernel, **kw), steps)
    return calls


def _flash_call(which, q, v, bias, scale, p_drop, causal):
    """``(call, leading operands)`` of flash kernel ``which`` for these
    inputs, its work counted."""
    B, H, S, d = q.shape
    tb = _flash_block(S)
    edges = (_diag_edge(tb, d, forward=True), _diag_edge(tb, d)) \
        if causal else (None, None)
    _count_flash_tiles(S // tb, causal)
    if causal:
        _count_causal_work("flash", S // tb, tb,
                           edges[0 if which == "fwd" else 1])
    call, steps = _flash_calls(q.shape, q.dtype, bias.shape[1], v.shape[-1],
                               scale, p_drop, causal, tb, edges,
                               _interpret())[which]
    return call, [jnp.asarray(t) for t in steps]


def _pallas_attention_flash(q, k, v, bias, scale, p_drop, seed,
                            causal=False):
    """Returns (o, lse): lse [B, H, S] f32 feeds the split backward."""

    _count_kernel("flash")
    call, steps = _flash_call("fwd", q, v, bias, scale, p_drop, causal)
    return call(*steps, seed, q, k, v, bias)


def _pallas_attention_flash_bwd(q, k, v, bias, seed, do, o, lse, scale,
                                p_drop, causal=False):
    _count_kernel("flash_bwd")
    B, H, S, d = q.shape
    TB = _flash_block(S)
    nt = S // TB
    f32 = jnp.float32
    dd = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1,
                 keepdims=True)                            # [B, H, S, 1]
    call, steps = _flash_call("dq", q, v, bias, scale, p_drop, causal)
    dq, dbp = call(*steps, seed, q, k, v, bias, do, lse, dd)
    call, steps = _flash_call("dkv", q, v, bias, scale, p_drop, causal)
    dk, dv = call(*steps, seed, q, k, v, bias, do, lse, dd)
    dbp = dbp.reshape(B, H, nt, S)
    if causal:      # the partials of the tiles skipped were never written
        dbp = jnp.where(jnp.arange(S)[None, :] // TB
                        <= jnp.arange(nt)[:, None], dbp, 0.0)
    dbias = jnp.sum(dbp, axis=2)[:, :, None, :]            # [B, H, 1, S]
    if bias.shape[1] == 1:
        dbias = jnp.sum(dbias, axis=1, keepdims=True)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# Select tier: attention under a per-(query, key) selection.
#
# ``select`` [B, S, S] (int8, nonzero = query t may see key s) is one set a
# (batch row, query), shared by all heads and not differentiable: a learned
# sparse attention's indexer makes it (``fluid/ops/sparse_attention.py``).
# The kernels are the flash tier's online softmax and split backward with
# the selection added to each score tile as 0 / -1e30. A grid step holds
# the G = H / Hkv query heads of ONE K/V head: the selection's tile, its
# 0 / -1e30 form and the K/V tile are fetched and made once a step and
# serve the group, so the selection is read Hkv times a call, not H
# times, and K and V are never repeated to the Q head count. Causal only
# (a learned selection picks among the keys before the query): the grid
# walks the causal tiles alone, in the flash tier's orders
# (``_causal_steps``), and the diagonal tile in its strips
# (``_diag_strips``), column > row masked in the strips' diagonal blocks
# beside the selection. No bias and no dropout (a caller with either gets
# an error).
# ---------------------------------------------------------------------------
# Largest first: measured v5e B=1, H=32, Hkv=4, S=16384, d=128 (PR 32), ms a
# call forward / dq / dk-dv: 17.4 / 20.0 / 25.9 at Tb=1024, 29.8 / 21.5 / 31.5
# at 512 (the flash tier without a selection, K/V repeated: 18.4 / 22.4 / 27.4).
_SELECT_BLOCK_CANDIDATES = (1024, 512, 256, 128)
_SELECT_MAX_GROUP = 8       # what the VMEM limit below was sized for

# At Tb=1024 and G=8 a step holds ~45 MB (a group's q / o / do blocks and
# row statistics double-buffered, the f32 carries, a few [Tb, Tb] f32
# tiles): over Mosaic's 16 MB default and the flash tier's 32; the v5e
# compiler takes the cell's shape at 48. The chip has 128 MiB of VMEM.
_SELECT_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=64 * 1024 * 1024)


def _for_heads(G, body):
    """``body(g)`` for each of a step's G heads, one after the other (a
    ``fori_loop``: unrolled, the kernels read 0.8 ms of 65 faster a call
    and compile in 18 s, not 2)."""
    jax.lax.fori_loop(0, G, lambda g, c: (body(g), c)[1], 0)


def _select_block(S):
    for tb in _SELECT_BLOCK_CANDIDATES:
        if S % tb == 0:
            return tb
    return None


def _select_group(H, Hkv):
    """G, the query heads of a K/V head (a grid step's heads), or None
    where the heads do not divide or the group is wider than the kernels'
    VMEM limit was sized for."""
    if Hkv <= 0 or H % Hkv or H // Hkv > _SELECT_MAX_GROUP:
        return None
    return H // Hkv


def _use_select_kernel(q, k, causal):
    return (causal and _supports_pallas()
            and _select_block(q.shape[2]) is not None
            and _select_group(q.shape[1], k.shape[1]) is not None)


def count_select_pairs(S, topk, sites=1):
    """Trace-time record, once a traced selection site, of the (query,
    key) pairs a (batch row, head) that a top-``topk`` selection keeps and
    of the causal pairs it chose from."""
    from ..fluid import monitor as _monitor

    k = min(int(topk), S)
    kept = k * (k + 1) // 2 + (S - k) * k
    for kind, n in (("kept", kept), ("causal", S * (S + 1) // 2)):
        _monitor.counter(
            "attn_select_pairs_total",
            "(query, key) pairs a (batch row, head) under a learned top-k "
            "selection: kept, and the causal pairs chosen from "
            "(trace-time: once a traced selection site, not per step)",
            labels={"kind": kind}).inc(n * sites)


def _neg(keep):
    return jnp.where(keep, 0.0, -1e30).astype(jnp.float32)


def _select_neg(sel_ref, neg_scr, edge=None):
    """The step's selection tile as what is added to a score: 0 where the
    query may see the key, else -1e30; made once, read by every head.
    With ``edge`` (the diagonal tile) only its strips' parts are made
    (``_diag_strips``), column > row masked in their diagonal blocks."""
    if edge is None:
        neg_scr[...] = _neg(sel_ref[0].astype(jnp.int32) != 0)
        return
    for rows, parts in _diag_strips(neg_scr.shape[0], edge, rows=True):
        for cols, diag in parts:
            keep = sel_ref[0, rows, cols].astype(jnp.int32) != 0
            if diag:
                keep = jnp.logical_and(keep, (
                    jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
                    <= jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0)))
            neg_scr[rows, cols] = _neg(keep)


def _select_fwd_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, sel_ref, o_ref,
                       lse_ref, neg_scr, acc_scr, m_scr, l_scr, *, scale, G,
                       edge):
    """Grid (B, H/G, nt(nt + 1) / 2) over the causal tiles row by row
    (``_causal_steps``): the flash forward for each of the step's G heads
    in turn, the diagonal tile strip by strip. m starts at -1e29, above a
    masked score: a row that has seen no selected key yet keeps p =
    exp(-1e30 + 1e29) = 0 and l = 0, and the first selected key's corr =
    exp(-1e29 - m) = 0 starts it."""
    t = pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = neg_scr.shape[0]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e29, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j < i)
    def _below():
        _select_neg(sel_ref, neg_scr)

        def head(g):
            s = _scores(q_ref[0, g], k_ref[0, 0], scale) + neg_scr[...]
            _online_softmax(s, v_ref[0, 0], m_scr, l_scr, acc_scr, g)

        _for_heads(G, head)

    @pl.when(j == i)
    def _diagonal():
        _select_neg(sel_ref, neg_scr, edge)

        def head(g):
            for o in range(0, tb, edge):
                rows, keys = slice(o, o + edge), slice(0, o + edge)
                s = (_scores(q_ref[0, g, rows], k_ref[0, 0, keys], scale)
                     + neg_scr[rows, keys])
                _online_softmax(s, v_ref[0, 0, keys], m_scr, l_scr, acc_scr,
                                (g, rows))
            l = l_scr[g]
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_scr[g] + jnp.log(l)

        _for_heads(G, head)


def _select_dq_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, sel_ref, do_ref,
                      lse_ref, dd_ref, dq_ref, neg_scr, *, scale, G, edge):
    """Grid (B, H/G, nt(nt + 1) / 2) over the causal tiles row by row: dq
    of the step's G heads, gathered over the row's tiles; p = exp(s - L)
    from the saved logsumexp, 0 where the selection masks."""
    t = pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = neg_scr.shape[0]

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

    @pl.when(j < i)
    def _below():
        _select_neg(sel_ref, neg_scr)

        def head(g):
            k = k_ref[0, 0]
            s = _scores(q_ref[0, g], k, scale) + neg_scr[...]
            ds = _ds(s, v_ref[0, 0], do_ref[0, g], lse_ref[0, g],
                     dd_ref[0, g])
            dq_ref[0, g] += _dq_of(ds, k) * scale

        _for_heads(G, head)

    @pl.when(j == i)
    def _diagonal():
        _select_neg(sel_ref, neg_scr, edge)

        def head(g):
            for rows, parts in _diag_strips(tb, edge, rows=True):
                q, do = q_ref[0, g, rows], do_ref[0, g, rows]
                lse, dd = lse_ref[0, g, rows], dd_ref[0, g, rows]
                dqs = []
                for cols, _ in parts:
                    k = k_ref[0, 0, cols]
                    s = _scores(q, k, scale) + neg_scr[rows, cols]
                    dqs.append(_dq_of(_ds(s, v_ref[0, 0, cols], do, lse, dd),
                                      k))
                dq_ref[0, g, rows] += _total(dqs) * scale

        _for_heads(G, head)


def _select_dkdv_kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, sel_ref, do_ref,
                        lse_ref, dd_ref, dk_ref, dv_ref, neg_scr, *, scale, G,
                        edge):
    """Grid (B, Hkv, nt(nt + 1) / 2) over the causal tiles k-tile by
    k-tile, each column from its diagonal tile down: dk and dv of the
    step's K/V head, gathered over the column's tiles and over its G query
    heads (f32, [B, Hkv, S, d]: no repeated K/V to sum back); the
    diagonal tile in column strips."""
    t = pl.program_id(2)
    i, j = qt_ref[t], kt_ref[t]
    tb = neg_scr.shape[0]

    @pl.when(j < i)
    def _below():
        _select_neg(sel_ref, neg_scr)

        def head(g):
            q = q_ref[0, g]
            s = _scores(q, k_ref[0, 0], scale) + neg_scr[...]
            dk, dv = _dkdv(s, q, v_ref[0, 0], do_ref[0, g], lse_ref[0, g],
                           dd_ref[0, g])
            dv_ref[0, 0] += dv
            dk_ref[0, 0] += dk * scale

        _for_heads(G, head)

    @pl.when(j == i)
    def _diagonal():
        dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
        dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)
        _select_neg(sel_ref, neg_scr, edge)

        def head(g):
            for cols, parts in _diag_strips(tb, edge, rows=False):
                k, v = k_ref[0, 0, cols], v_ref[0, 0, cols]
                dks, dvs = [], []
                for rows, _ in parts:
                    q = q_ref[0, g, rows]
                    s = _scores(q, k, scale) + neg_scr[rows, cols]
                    dk, dv = _dkdv(s, q, v, do_ref[0, g, rows],
                                   lse_ref[0, g, rows], dd_ref[0, g, rows])
                    dks.append(dk)
                    dvs.append(dv)
                dv_ref[0, 0, cols] += _total(dvs)
                dk_ref[0, 0, cols] += _total(dks) * scale

        _for_heads(G, head)


def _select_specs(q, k):
    """Block specs of the select kernels' grid ``(B, Hkv, step)`` over the
    causal tiles, a step's tiles from the tables (``_causal_steps``),
    whatever their order."""
    B, H, S, d = q.shape
    G = _select_group(H, k.shape[1])
    TB = _select_block(S)
    nt = S // TB

    def spec(block, index):
        return pl.BlockSpec(block, lambda b, h, t, qt, kt: index(
            b, h, qt[t], kt[t]))

    qspec = spec((1, G, TB, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = spec((1, 1, TB, d), lambda b, h, i, j: (b, h, j, 0))
    sspec = spec((1, TB, TB), lambda b, h, i, j: (b, i, j))
    rowspec = spec((1, G, TB, 1), lambda b, h, i, j: (b, h, i, 0))
    return G, TB, nt, qspec, kspec, sspec, rowspec


@functools.lru_cache(maxsize=None)
def _select_calls(shape, kv_shape, dtype, scale, tb, edges, interpreted):
    """The select tier's three ``pallas_call``s of one signature - q's
    ``shape`` and ``dtype``, K/V's ``kv_shape`` - built ONCE, as
    ``_flash_calls`` builds the flash tier's; ``tb``, ``edges`` and
    ``interpreted`` are part of the key only."""
    B, H, S, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype)
    G, TB, nt, qspec, kspec, sspec, rowspec = _select_specs(
        q, jax.ShapeDtypeStruct(kv_shape, dtype))
    assert TB == tb, (TB, tb)
    f32 = jnp.float32
    neg = pltpu.VMEM((TB, TB), f32)

    def sweep(kernel, edge, out_shape, by_row=True, **specs):
        """A sweep's kernel, ``pallas_call`` keywords and leading
        operands."""
        kw, steps = _sweep(True, B, H // G, nt, by_row, **specs)
        return functools.partial(kernel, scale=scale, G=G, edge=edge), dict(
            kw, out_shape=out_shape,
            compiler_params=_SELECT_COMPILER_PARAMS), steps

    kernel, kw, steps = sweep(
        _select_fwd_kernel, edges[0],
        [jax.ShapeDtypeStruct(shape, dtype),
         jax.ShapeDtypeStruct((B, H, S, 1), f32)],
        in_specs=[qspec, kspec, kspec, sspec], out_specs=[qspec, rowspec],
        scratch_shapes=[neg, pltpu.VMEM((G, TB, d), f32),
                        pltpu.VMEM((G, TB, 1), f32),
                        pltpu.VMEM((G, TB, 1), f32)])
    calls = {"fwd": (_kernel_call("attn_select_fwd", kernel, **kw), steps)}
    bwd_in = [qspec, kspec, kspec, sspec, qspec, rowspec, rowspec]
    kernel, kw, steps = sweep(
        _select_dq_kernel, edges[1], jax.ShapeDtypeStruct(shape, f32),
        in_specs=bwd_in, out_specs=qspec, scratch_shapes=[neg])
    calls["dq"] = (_kernel_call("attn_select_bwd_dq", kernel, **kw), steps)
    kernel, kw, steps = sweep(
        _select_dkdv_kernel, edges[1],
        [jax.ShapeDtypeStruct(kv_shape, f32)] * 2, by_row=False,
        in_specs=bwd_in, out_specs=[kspec, kspec], scratch_shapes=[neg])
    calls["dkv"] = (_kernel_call("attn_select_bwd_dkv", kernel, **kw),
                    steps)
    return calls


def _select_call(which, q, k, scale):
    """``(call, leading operands)`` of select kernel ``which`` for these
    inputs, its work counted."""
    _count_kernel("select")
    S, d = q.shape[2:]
    tb = _select_block(S)
    edges = (_diag_edge(tb, d, forward=True), _diag_edge(tb, d))
    _count_causal_work("select", S // tb, tb,
                       edges[0 if which == "fwd" else 1],
                       heads=_select_group(q.shape[1], k.shape[1]))
    call, steps = _select_calls(q.shape, k.shape, q.dtype, scale, tb, edges,
                                _interpret())[which]
    return call, [jnp.asarray(t) for t in steps]


def _pallas_attention_select(q, k, v, select, scale):
    """Returns (o, lse); k, v at their own head count [B, Hkv, S, d]."""
    call, steps = _select_call("fwd", q, k, scale)
    return call(*steps, q, k, v, select)


def _pallas_attention_select_bwd(q, k, v, select, do, o, lse, scale):
    f32 = jnp.float32
    dd = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1, keepdims=True)
    call, steps = _select_call("dq", q, k, scale)
    dq = call(*steps, q, k, v, select, do, lse, dd)
    call, steps = _select_call("dkv", q, k, scale)
    dk, dv = call(*steps, q, k, v, select, do, lse, dd)
    return dq, dk, dv


def _select_fallback(q, k, v, select, scale, causal):
    """Off the kernels: the selection as a [B, 1, S, S] additive bias on
    ``_ref_attention`` / ``_blockwise_attention`` (K/V repeated to the Q
    head count) - the kernels' oracle."""
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    bias = jnp.where(select != 0, 0.0, -1e30).astype(jnp.float32)[:, None]
    return _fallback_attention(q, k, v, bias, scale, 0.0, None, causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _selected(q, k, v, select, scale, causal):
    if _use_select_kernel(q, k, causal):
        return _pallas_attention_select(q, k, v, select, scale)[0]
    return _select_fallback(q, k, v, select, scale, causal)


def _selected_fwd(q, k, v, select, scale, causal):
    if _use_select_kernel(q, k, causal):
        o, lse = (keep_across_recompute(x, "attn_select") for x in
                  _pallas_attention_select(q, k, v, select, scale))
        return o, (q, k, v, select, (o, lse))
    return (_select_fallback(q, k, v, select, scale, causal),
            (q, k, v, select, None))


def _selected_bwd(scale, causal, res, do):
    q, k, v, select, kernel_res = res
    if kernel_res is not None:
        o, lse = kernel_res
        dq, dk, dv = _pallas_attention_select_bwd(
            q, k, v, select, do, o, lse, scale)
        dq, dk, dv = (dq.astype(q.dtype), dk.astype(k.dtype),
                      dv.astype(v.dtype))
    else:
        _, vjp = jax.vjp(lambda q_, k_, v_: _select_fallback(
            q_, k_, v_, select, scale, causal), q, k, v)
        dq, dk, dv = vjp(do)
    return dq, dk, dv, np.zeros(select.shape, dtype=jax.dtypes.float0)


_selected.defvjp(_selected_fwd, _selected_bwd)


def _batch_block(B, S, tile_budget):
    """Largest divisor of B whose [Bb, S, S] fp32 score tile stays under
    ``tile_budget`` bytes (the fwd kernel holds ~4 such temporaries, the
    bwd ~8 — budgets sized so either fits 16 MB VMEM)."""
    cap = max(1, tile_budget // (S * S * 4))
    bb = 1
    for c in range(1, min(B, cap) + 1):
        if B % c == 0:
            bb = c
    return bb


def _specs(q, bias, tile_budget=2 * 1024 * 1024):
    B, H, S, d = q.shape
    Bb = _batch_block(B, S, tile_budget)
    grid = (B // Bb, H)
    qspec = pl.BlockSpec((Bb, 1, S, d), lambda b, h: (b, h, 0, 0))
    sspec = pl.BlockSpec((Bb, 1, S, S), lambda b, h: (b, h, 0, 0))
    bspec = pl.BlockSpec((Bb, 1, bias.shape[2], S),
                         lambda b, h, _nb=bias.shape[1]:
                         (b, h if _nb > 1 else 0, 0, 0))
    return grid, qspec, sspec, bspec


_BWD_BUDGET = 512 * 1024  # ~8 live [Bb, S, S] f32 temporaries


def _fwd_budget(p_drop):
    """With dropout the fwd must pick the SAME batch block as the bwd —
    the per-(block, head) PRNG draw shapes must line up for the
    regenerated mask to be bit-exact."""
    return _BWD_BUDGET if p_drop > 0.0 else 2 * 1024 * 1024


def _pallas_attention(q, k, v, bias, scale, p_drop, seed, causal=False):
    _count_kernel("block")
    B, H, S, d = q.shape
    grid, qspec, _, bspec = _specs(q, bias,
                                   tile_budget=_fwd_budget(p_drop))
    return _kernel_call(
        "attn_block_fwd",
        functools.partial(_fwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=H, causal=causal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, qspec, qspec, bspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(seed, q, k, v, bias)


def _pallas_attention_bwd(q, k, v, bias, seed, do, scale, p_drop,
                          causal=False):
    _count_kernel("block_bwd")
    B, H, S, d = q.shape
    grid, qspec, sspec, bspec = _specs(q, bias, tile_budget=_BWD_BUDGET)
    acc_heads = bias.shape[1] == 1
    reduce_rows = bias.shape[2] == 1
    dbias_shape = (B, bias.shape[1], bias.shape[2], S)
    f32 = jnp.float32
    dq, dk, dv, dbias = _kernel_call(
        "attn_block_bwd",
        functools.partial(_bwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=H, acc_heads=acc_heads,
                          reduce_rows=reduce_rows, causal=causal),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, qspec, qspec, bspec, qspec],
        out_specs=[qspec, qspec, qspec, bspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(dbias_shape, f32)],
    )(seed, q, k, v, bias, do)
    return dq, dk, dv, dbias


def _use_kernel(q, p_drop):
    """The TPU PRNG primitives have no CPU-interpreter lowering, so
    dropout kernels only run on real TPU; everything else also runs
    under interpret mode in CI."""
    if not _supports_pallas() or q.shape[2] > _MAX_FUSED_SEQ:
        return False
    return not (_interpret() and p_drop > 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 7))
def _fused(q, k, v, bias, scale, p_drop, seed, causal=False):
    two = v.shape[-1] != q.shape[-1]    # v of its own width: flash only
    if not two and _use_kernel(q, p_drop):
        return _pallas_attention(q, k, v, bias, scale, p_drop, seed, causal)
    if not two and _use_long_kernel(q, p_drop, bias):
        return _pallas_attention_long(q, k, v, bias, scale, p_drop, seed,
                                      causal)
    if _use_flash_kernel(q, p_drop, bias, v):
        return _pallas_attention_flash(q, k, v, bias, scale, p_drop,
                                       seed, causal)[0]
    return _fallback_attention(q, k, v, bias, scale, p_drop, seed, causal)


def _fused_fwd(q, k, v, bias, scale, p_drop, seed, causal=False):
    if _use_flash_kernel(q, p_drop, bias, v):
        # the split backward regenerates probabilities from the row
        # logsumexp and needs rowsum(do*o), so o and lse join the
        # residuals (flash-attention-2 residual set: q, k, v, o, L)
        o, lse = (keep_across_recompute(x, "attn_flash") for x in
                  _pallas_attention_flash(q, k, v, bias, scale, p_drop,
                                          seed, causal))
        return o, (q, k, v, bias, seed, (o, lse))
    out = _fused(q, k, v, bias, scale, p_drop, seed, causal)
    return out, (q, k, v, bias, seed, None)


def _fused_bwd(scale, p_drop, causal, res, do):
    q, k, v, bias, seed, flash_res = res
    if flash_res is not None:
        o, lse = flash_res
        dq, dk, dv, dbias = _pallas_attention_flash_bwd(
            q, k, v, bias, seed, do, o, lse, scale, p_drop, causal)
        return (dq.astype(q.dtype), dk.astype(q.dtype), dv.astype(q.dtype),
                dbias.astype(bias.dtype), _seed_ct(seed))
    two = v.shape[-1] != q.shape[-1]
    if not two and _use_kernel(q, p_drop):
        dq, dk, dv, dbias = _pallas_attention_bwd(q, k, v, bias, seed, do,
                                               scale, p_drop, causal)
    elif not two and _use_long_kernel(q, p_drop, bias):
        dq, dk, dv, dbias = _pallas_attention_long_bwd(
            q, k, v, bias, seed, do, scale, p_drop, causal)
    else:
        # recompute-based vjp through the fallback path (blockwise past
        # the VMEM bound: remat'd scan keeps bwd memory at the per-step
        # carries, O(nb*S*d) — see _blockwise_attention)
        def f(q_, k_, v_, bias_):
            return _fallback_attention(q_, k_, v_, bias_, scale, p_drop,
                                       seed, causal)

        _, vjp = jax.vjp(f, q, k, v, bias)
        dq, dk, dv, dbias = vjp(do)
        return dq, dk, dv, dbias, _seed_ct(seed)
    # dbias is already reduced to the bias broadcast shape in-kernel
    return dq, dk, dv, dbias.astype(bias.dtype), _seed_ct(seed)


def _seed_ct(seed):
    """Cotangent for an integer input is float0 (jax's tangent type)."""
    return np.zeros(seed.shape, dtype=jax.dtypes.float0)


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(q, k, v, bias=None, scale=None, dropout_prob=0.0,
                    rng_key=None, causal=False, select=None):
    """softmax(q·kᵀ·scale + bias)·v fused per (batch, head).

    q/k/v: [B, H, S, d]; bias broadcastable [B, 1|H, 1|S, S] additive
    (0 keep / -1e4 mask); returns [B, H, S, d] in q's dtype. v may have a
    width ``dv`` of its own ([B, H, S, dv]; latent attention: 192-wide q
    and k, 128-wide v): the result is then [B, H, S, dv], the flash tier
    serves every S that a tile divides (the block and long tiers hold one
    width) and the jnp forms everything else. ``causal``
    masks column > row inside the kernels, by index: no [S, S] bias, so
    the flash tier (row-broadcast bias only) stays open to it.
    ``select`` [B, S, S] (integer, nonzero = the query may see the key;
    shared by all heads, no gradient) takes the select tier: k and v then
    come at their OWN head count [B, Hkv, S, d], and neither a bias nor
    dropout goes with it.
    """
    B, H, S, d = q.shape
    if select is not None:
        if bias is not None or dropout_prob:
            raise NotImplementedError(
                "fused_attention: select goes with neither a bias nor "
                "dropout")
        assert select.shape == (B, S, S), (select.shape, q.shape)
        return _selected(q, k, v, select,
                         float(scale) if scale else 1.0 / math.sqrt(d),
                         bool(causal))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if bias is None:
        bias = jnp.zeros((B, 1, 1, S), jnp.float32)
    bias = jnp.broadcast_to(bias.astype(jnp.float32),
                            (B, bias.shape[1], bias.shape[2], S))
    if dropout_prob > 0.0:
        if rng_key is None:
            raise ValueError("dropout_prob > 0 requires rng_key")
        seed = jax.random.randint(rng_key, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    return _fused(q, k, v, bias, float(scale), float(dropout_prob), seed,
                  bool(causal))


# ---------------------------------------------------------------------------
# Incremental decode: KV ring-buffer update + cache-aware attention.
#
# Inference-only (no custom_vjp): the decode program is traced once with a
# fixed cache CAPACITY C, so every per-token step reuses one executable.
# The cache is a ring buffer — token t lands at slot t % C, and once more
# than C tokens have been written the buffer holds the most recent C in
# scrambled slot order, which is fine because softmax attention is
# permutation-invariant over the key axis.
# ---------------------------------------------------------------------------

def kv_cache_update(cache, new, cache_len):
    """Write ``new`` [B, H, T, d] into the ring buffer ``cache``
    [B, H, C, d] at per-sequence slot ``cache_len % C`` and return
    ``(updated_cache, cache_len + T)``.

    ``cache_len`` [B] int32 counts TOTAL tokens ever written per
    sequence (it is not clamped to C — the ring position and the
    valid-length mask are both derived from it). A single write must not
    cross the ring boundary: (cache_len % C) + T <= C per sequence.
    Decode steps (T=1) always satisfy this; prefill writes start at
    cache_len=0 and need prompt length <= C."""
    B, H, C, d = cache.shape
    T = new.shape[2]
    lens = jnp.reshape(cache_len, (B,)).astype(jnp.int32)
    pos = jnp.mod(lens, jnp.int32(C))

    def upd(c, n, p):
        return jax.lax.dynamic_update_slice(c, n, (0, p, 0))

    out = jax.vmap(upd)(cache, new.astype(cache.dtype), pos)
    return out, lens + jnp.int32(T)


def _ref_attention_cache(q, k_cache, v_cache, cache_len, scale,
                         causal_window=False):
    """Masked-length fallback (and the numerics oracle in tests): fp32
    scores over the FULL capacity, slots at column >= min(cache_len, C)
    masked to -1e30 (not -inf: an exp(-inf - -inf) NaN would poison
    rows), softmax, PV.

    ``causal_window=True`` is the speculative-verify form: the Q rows
    are ``cache_len`` - Q .. ``cache_len`` - 1 in sequence order (the
    last Q tokens just written), so row r additionally masks the
    columns written AFTER it — col < valid - (Q-1-r). Slot index ==
    sequence position is assumed (no ring wraparound), which the
    speculative session asserts at build time."""
    B, H, Q, d = q.shape
    C = k_cache.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    valid = jnp.minimum(jnp.reshape(cache_len, (B,)).astype(jnp.int32),
                        jnp.int32(C))
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, C), 3)
    limit = valid.reshape(B, 1, 1, 1)
    if causal_window:
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, Q, 1), 2)
        limit = limit - jnp.int32(Q - 1) + row
    s = jnp.where(col < limit, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


_DECODE_KB_CANDIDATES = (512, 256, 128)


def _decode_kb(C):
    for kb in _DECODE_KB_CANDIDATES:
        if C % kb == 0:
            return kb
    return None


def _use_decode_kernel(k_cache):
    """Pallas decode tier: same dispatch shape as training attention —
    the capacity >= ``_MAX_FUSED_SEQ`` regime where the Pallas tiers
    win."""
    return _supports_pallas() and k_cache.shape[2] >= _MAX_FUSED_SEQ


def _decode_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_scr, m_scr, l_scr, *, scale, kb, nk,
                       causal_window=False):
    """Grid (B, H, nk), k-block fastest: online softmax over cache
    blocks, same (m, l, acc) VMEM-scratch carry as the flash forward.
    The per-sequence valid length rides whole-array in SMEM; columns at
    or past it (including ring capacity padding) mask to -1e30.
    ``causal_window`` shifts the per-row limit for the speculative
    verify step (row r of Q sees col < len - (Q-1-r))."""

    b, j = pl.program_id(0), pl.program_id(2)
    q = q_ref[0, 0]                               # [Q, d]
    k = k_ref[0, 0]                               # [KB, d]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = j * kb + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    limit = len_ref[b]
    if causal_window:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        limit = limit - (s.shape[0] - 1) + row
    s = jnp.where(col < limit, s, -1e30)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    m_prev = m_scr[...]                           # [Q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                        # [Q, KB]
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _pallas_attention_decode(q, k_cache, v_cache, cache_len, scale,
                             causal_window=False):
    _count_kernel("decode")
    B, H, Q, d = q.shape
    C = k_cache.shape[2]
    KB = _decode_kb(C)
    if KB is None:
        # a capacity no candidate divides (odd, prime): pad the cache to the
        # next 128 multiple — padded columns sit past the valid length
        # and mask out like any empty slot
        KB = _DECODE_KB_CANDIDATES[-1]
        pad = (-C) % KB
        zeros = jnp.zeros((B, H, pad, d), k_cache.dtype)
        k_cache = jnp.concatenate([k_cache, zeros], axis=2)
        v_cache = jnp.concatenate([v_cache, zeros], axis=2)
    nk = k_cache.shape[2] // KB
    lens = jnp.minimum(jnp.reshape(cache_len, (B,)).astype(jnp.int32),
                       jnp.int32(C))
    qspec = pl.BlockSpec((1, 1, Q, d), lambda b, h, j: (b, h, 0, 0))
    kspec = pl.BlockSpec((1, 1, KB, d), lambda b, h, j: (b, h, j, 0))
    f32 = jnp.float32
    return _kernel_call(
        "attn_decode",
        functools.partial(_decode_fwd_kernel, scale=scale, kb=KB, nk=nk,
                          causal_window=causal_window),
        grid=(B, H, nk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((Q, d), f32),
                        pltpu.VMEM((Q, 1), f32),
                        pltpu.VMEM((Q, 1), f32)],
    )(lens, q, k_cache, v_cache)


def attention_with_cache(q, k_cache, v_cache, cache_len, scale=None,
                         causal_window=False):
    """Decode-step attention against a KV ring buffer.

    q [B, H, Q, d] (Q=1 for incremental decode), k_cache/v_cache
    [B, H, C, d], cache_len [B] int32 = tokens written so far per
    sequence (post-update, so the current token attends to itself;
    must be >= 1). Only the first min(cache_len, C) slots participate;
    slot order does not matter (softmax is permutation-invariant), so
    ring wraparound needs no unscrambling. Returns [B, H, Q, d] in q's
    dtype. Inference-only: no backward.

    ``causal_window=True`` (speculative verify, Q > 1): row r of Q is
    the token at sequence position cache_len - Q + r, so it masks the
    columns written after it (col < cache_len - (Q-1-r)). Requires
    slot index == position, i.e. cache_len <= C (no wraparound)."""
    B, H, Q, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = float(scale)
    if _use_decode_kernel(k_cache):
        return _pallas_attention_decode(q, k_cache, v_cache, cache_len,
                                        scale,
                                        causal_window=causal_window)
    return _ref_attention_cache(q, k_cache, v_cache, cache_len, scale,
                                causal_window=causal_window)


# ---------------------------------------------------------------------------
# Paged KV: a SHARED block pool [P, H, ptok, d] indexed by per-slot page
# tables [B, npages] (vLLM's PagedAttention layout). A slot's logical ring
# position p (= cache_len % capacity, capacity = npages*ptok) lives at
# pool row table[b, p // ptok], offset p % ptok — so the paged cache is
# BIT-identical to the dense ring of the same capacity, including
# wraparound, and HBM is bounded by live tokens (allocated pages), not
# B x capacity. Page 0 is the never-allocated SCRATCH page: table entries
# of idle slots and not-yet-allocated regions point at it, so the
# shape-closed decode program can write every step unconditionally —
# scratch absorbs the garbage, allocation/COW stay host-side table edits.
# ---------------------------------------------------------------------------

def paged_kv_cache_update(pool, new, page_table, cache_len):
    """Write ``new`` [B, H, T, d] through the page table into the shared
    pool [P, H, ptok, d] and return ``(updated_pool, cache_len + T)``.

    ``page_table`` [B, npages] int32 maps each slot's logical page j to
    a pool row; token t of ``new`` lands at logical ring position
    (cache_len + t) % (npages * ptok). Unlike the dense ring's
    ``kv_cache_update`` a write MAY cross page (and ring) boundaries —
    each token scatters independently. Rows of different slots must map
    to disjoint writable pages (the session's free list guarantees it);
    duplicate scratch-page writes are harmless garbage."""
    P, H, ptok, d = pool.shape
    B, _, T, _ = new.shape
    npages = page_table.shape[1]
    cap = npages * ptok
    lens = jnp.reshape(cache_len, (B,)).astype(jnp.int32)
    pos = jnp.mod(lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :],
                  jnp.int32(cap))                          # [B, T]
    page = jnp.take_along_axis(page_table.astype(jnp.int32),
                               pos // jnp.int32(ptok), axis=1)  # [B, T]
    off = jnp.mod(pos, jnp.int32(ptok))
    vals = jnp.transpose(new.astype(pool.dtype),
                         (0, 2, 1, 3)).reshape(B * T, H, d)
    out = pool.at[page.reshape(-1), :, off.reshape(-1), :].set(vals)
    return out, lens + jnp.int32(T)


def gather_paged_cache(pool, page_table):
    """Materialize the dense [B, H, capacity, d] view of a paged cache —
    the fallback attention path and the paged<->dense equivalence oracle
    in tests. Pure gather: pool rows in table order, pages concatenated
    along the slot axis."""
    B = page_table.shape[0]
    # [B, npages, H, ptok, d] -> [B, H, npages*ptok, d]
    g = jnp.take(pool, page_table.astype(jnp.int32).reshape(-1), axis=0)
    g = g.reshape(B, page_table.shape[1], *pool.shape[1:])
    g = jnp.transpose(g, (0, 2, 1, 3, 4))
    return g.reshape(B, pool.shape[1], -1, pool.shape[3])


def _use_paged_kernel(page_table, ptok):
    """Same dispatch shape as the dense decode tier: the big-capacity
    regime, gated on Pallas availability."""
    return (_supports_pallas()
            and page_table.shape[1] * ptok >= _MAX_FUSED_SEQ)


def _paged_decode_fwd_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref,
                             o_ref, acc_scr, m_scr, l_scr, *, scale,
                             ptok, npages):
    """Grid (B, H, npages), page fastest: the dense decode kernel's
    online softmax, except each k/v block is DMA'd from whatever pool
    row the SMEM page table names — the gather never materializes a
    dense [B, H, C, d] cache. tab_ref/len_ref are the scalar-prefetch
    operands (PrefetchScalarGridSpec passes them to the kernel AND to
    every BlockSpec index map)."""

    b, j = pl.program_id(0), pl.program_id(2)
    q = q_ref[0, 0]                               # [Q, d]
    k = k_ref[0, 0]                               # [ptok, d]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    col = j * ptok + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < len_ref[b], s, -1e30)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    m_prev = m_scr[...]                           # [Q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                        # [Q, ptok]
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _pallas_attention_paged(q, k_pool, v_pool, page_table, cache_len,
                            scale):
    _count_kernel("paged")
    B, H, Q, d = q.shape
    P, _, ptok, _ = k_pool.shape
    npages = page_table.shape[1]
    cap = npages * ptok
    table = page_table.astype(jnp.int32)
    lens = jnp.minimum(jnp.reshape(cache_len, (B,)).astype(jnp.int32),
                       jnp.int32(cap))
    # index maps receive the scalar-prefetch refs as trailing args: the
    # k/v block for grid cell (b, h, j) is pool row table[b, j] — the
    # page-table indirection happens in the DMA schedule, not the graph
    qspec = pl.BlockSpec((1, 1, Q, d), lambda b, h, j, tab, ln:
                         (b, h, 0, 0))
    kspec = pl.BlockSpec((1, 1, ptok, d), lambda b, h, j, tab, ln:
                         (tab[b, j], h, 0, 0))
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, npages),
        in_specs=[qspec, kspec, kspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((Q, d), f32),
                        pltpu.VMEM((Q, 1), f32),
                        pltpu.VMEM((Q, 1), f32)])
    return _kernel_call(
        "attn_paged",
        functools.partial(_paged_decode_fwd_kernel, scale=scale,
                          ptok=ptok, npages=npages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(table, lens, q, k_pool, v_pool)


def paged_attention_cache(q, k_pool, v_pool, page_table, cache_len,
                          scale=None):
    """Decode-step attention against a PAGED KV cache.

    q [B, H, Q, d] (Q=1), pools [P, H, ptok, d], page_table [B, npages]
    int32, cache_len [B] int32 (post-update). Valid slots are the first
    min(cache_len, npages*ptok) logical positions in page-table order;
    masking and numerics match the dense ``attention_with_cache`` of
    the gathered cache bit-for-bit (the token-identity contract the
    paged sessions rely on). Inference-only: no backward."""
    B, H, Q, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = float(scale)
    ptok = k_pool.shape[2]
    if _use_paged_kernel(page_table, ptok):
        return _pallas_attention_paged(q, k_pool, v_pool, page_table,
                                       cache_len, scale)
    dense_k = gather_paged_cache(k_pool, page_table)
    dense_v = gather_paged_cache(v_pool, page_table)
    return _ref_attention_cache(q, dense_k, dense_v, cache_len, scale)


# ---------------------------------------------------------------------------
# Sequence parallelism: S sharded over a mesh axis.
#
# Two strategies behind one entry point (``sequence_parallel_attention``):
#
#   ring    — every device keeps its own Q chunk; K/V chunks (plus the
#             row-broadcast bias column slice) rotate around the axis via
#             ``lax.ppermute``, one hop per shard. Each hop is ordinary
#             chunk-vs-chunk attention — the flash forward/backward kernels
#             when the chunk tiles, the jnp form otherwise — and the per-hop
#             (o, logsumexp) pairs merge online, so nothing [S, S]-shaped
#             ever exists and per-device attention memory is O((S/n)²).
#             Causal hops where the source chunk sits entirely in the
#             future are skipped under ``lax.cond`` (~halves average work).
#             The whole ring is one ``custom_vjp``: the backward is a
#             second ring pass in the flash-attention-2 style — the saved
#             GLOBAL logsumexp turns each hop's probabilities into global
#             softmax rows, so per-hop gradients are independent and the
#             dk/dv/dbias accumulators simply travel with their K/V chunk
#             (n rotations lands them home).
#
#   ulysses — ``lax.all_to_all`` trades the head axis for the sequence
#             axis ([B, H, S/n, d] -> [B, H/n, S, d]); each device then
#             runs FULL-sequence attention over its head subset through
#             the single-chip ``_fused`` dispatch, and the inverse
#             all_to_all restores the layout. Needs n | H; communicates
#             activations (2 all_to_alls) instead of K/V (n-1 hops).
#
# Dropout is shard-count-invariant: masks are generated per fixed
# ``_SP_DROP_TILE`` tile from a counter-based key fold
# (seed, global head, global q-tile, global k-tile), so the n-shard run
# reproduces the 1-shard run of the same op exactly — which is what the
# closeness tests assert. Denominator semantics match the rest of the
# file: softmax normalizes with UNDROPPED weights, only the value
# accumulation is masked.
# ---------------------------------------------------------------------------

_SP_DROP_TILE = 64


def _sp_dropout_keep(seed, batch_ids, head_ids, q_tile0, k_tile0, sq, sk,
                     p_drop):
    """Tiled keep-mask [B, H, sq, sk] for the local (q-chunk, k-chunk)
    pair. Each [T, T] tile draws from fold_in(seed, GLOBAL batch index,
    GLOBAL head, GLOBAL q-tile, GLOBAL k-tile) — fully position-keyed,
    so every shard of a run (over the sequence axis AND the batch axis)
    regenerates exactly the tiles of the equivalent single-shard run.
    All ids/offsets may be traced (they come from mesh ranks)."""
    T = _SP_DROP_TILE
    nqt, nkt = sq // T, sk // T
    base = jax.random.fold_in(jax.random.PRNGKey(0), seed[0])

    def tile(b, h, qt, kt):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(base, b), h), qt), kt)
        return jax.random.uniform(key, (T, T)) >= p_drop

    keep = jax.vmap(lambda b: jax.vmap(lambda h: jax.vmap(
        lambda qt: jax.vmap(lambda kt: tile(b, h, qt, kt))(
            k_tile0 + jnp.arange(nkt)))(
                q_tile0 + jnp.arange(nqt)))(head_ids))(batch_ids)
    # [B, H, nqt, nkt, T, T] -> [B, H, nqt*T, nkt*T]
    return jnp.transpose(keep, (0, 1, 2, 4, 3, 5)).reshape(
        batch_ids.shape[0], head_ids.shape[0], sq, sk)


def _sp_flash_ok(sq, p_drop):
    """A ring hop can run the Pallas flash pair when the chunk tiles
    (q and k chunks are the same size under even sharding) and there is
    no dropout — the flash kernels' in-kernel TPU PRNG cannot reproduce
    the shard-invariant tiled masks, so the dropout path stays jnp."""
    return (_supports_pallas() and p_drop == 0.0
            and _flash_block(sq) is not None)


def _diag_causal_mask(s):
    """Intra-chunk causal mask for the ring's diagonal hop: q and k carry
    the SAME global offset there, so the global triangle is the local
    one. -1e30, not -inf (NaN discipline, cf. _ref_attention_cache)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where((cols <= rows)[None, None], s, -1e30)


def _sp_hop_fwd(q, kb, vb, bias_b, scale, p_drop, keep, diag_causal):
    """One ring hop, jnp form: chunk-vs-chunk attention returning the
    NORMALIZED partial output and the row logsumexp (both f32) — the
    same (o, lse) contract as ``_pallas_attention_flash``, so the merge
    in the hop loop cannot tell the paths apart."""
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                   kb.astype(f32)) * scale + bias_b
    if diag_causal:
        s = _diag_causal_mask(s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    p = e / l
    if p_drop > 0.0:
        p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(f32))
    return o, m + jnp.log(l)


def _ring_fwd_pass(q, k, v, bias_k, seed, batch_ids, axis_name, n, causal,
                   scale, p_drop):
    """Forward ring: n hops, Python-unrolled (n is static), K/V/bias
    rotating between hops (the rotation after the last hop is elided —
    the inputs themselves are the residuals). Per-hop outputs merge via
    logsumexp: the result is bit-for-bit global softmax with the
    file-wide undropped-denominator dropout semantics."""
    B, H, sq, dh = q.shape
    r = jax.lax.axis_index(axis_name) if n > 1 else jnp.int32(0)
    perm = [(i, (i + 1) % n) for i in range(n)]
    T = _SP_DROP_TILE
    o = jnp.zeros((B, H, sq, dh), jnp.float32)
    lse = jnp.full((B, H, sq, 1), -1e30, jnp.float32)
    kb, vb, bkb = k, v, bias_k
    use_flash = _sp_flash_ok(sq, p_drop)
    head_ids = jnp.arange(H)
    for i in range(n):
        src = jnp.mod(r - i, n)     # whose K/V chunk this hop holds

        def hop(o_, lse_, kb=kb, vb=vb, bkb=bkb, src=src,
                diag=(causal and i == 0)):
            if use_flash and not diag:
                ob, lseb = _pallas_attention_flash(q, kb, vb, bkb, scale,
                                                   0.0, seed)
                ob = ob.astype(jnp.float32)
            else:
                keep = None
                if p_drop > 0.0:
                    keep = _sp_dropout_keep(seed, batch_ids, head_ids,
                                            r * (sq // T), src * (sq // T),
                                            sq, sq, p_drop)
                ob, lseb = _sp_hop_fwd(q, kb, vb, bkb, scale, p_drop,
                                       keep, diag)
            lse_new = jnp.logaddexp(lse_, lseb)
            return (o_ * jnp.exp(lse_ - lse_new)
                    + ob * jnp.exp(lseb - lse_new), lse_new)

        if causal and i > 0:
            # src is traced (depends on rank) -> runtime skip; only the
            # i==0 diagonal hop is statically known
            o, lse = jax.lax.cond(src > r, lambda o_, l_: (o_, l_), hop,
                                  o, lse)
        else:
            o, lse = hop(o, lse)
        if n > 1 and i < n - 1:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            bkb = jax.lax.ppermute(bkb, axis_name, perm)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _ring(q, k, v, bias_k, seed, batch_ids, axis_name, n, causal, scale,
          p_drop):
    """Ring attention over ``axis_name`` (shard-local view): q/k/v
    [B, H, S/n, d], bias_k [B, 1, 1, S/n] = this shard's bias columns,
    batch_ids [B] int32 = GLOBAL batch indices (dropout mask keys)."""
    return _ring_fwd_pass(q, k, v, bias_k, seed, batch_ids, axis_name, n,
                          causal, scale, p_drop)[0]


def _ring_fwd_rule(q, k, v, bias_k, seed, batch_ids, axis_name, n, causal,
                   scale, p_drop):
    o, lse = _ring_fwd_pass(q, k, v, bias_k, seed, batch_ids, axis_name,
                            n, causal, scale, p_drop)
    # flash-attention-2 residual set, ring edition: global o and global
    # row logsumexp make every hop's backward independent
    return o, (q, k, v, bias_k, seed, batch_ids, o, lse)


def _ring_bwd_rule(axis_name, n, causal, scale, p_drop, res, do):
    """Backward ring: a second pass over the same rotation schedule. The
    global lse turns exp(s - lse) into global softmax rows per hop, so
    ds = pd*dpd - p*rowsum(do*o) is exact per chunk (the flash split-
    kernel identity); dq accumulates locally while dk/dv/dbias
    accumulators travel WITH their K/V chunk — after n rotations each
    chunk (and its gradient) is back on its home device."""
    q, k, v, bias_k, seed, batch_ids, o, lse = res
    B, H, sq, dh = q.shape
    f32 = jnp.float32
    r = jax.lax.axis_index(axis_name) if n > 1 else jnp.int32(0)
    perm = [(i, (i + 1) % n) for i in range(n)]
    T = _SP_DROP_TILE
    do_f = do.astype(f32)
    dd = jnp.sum(do_f * o.astype(f32), axis=-1, keepdims=True)
    dq = jnp.zeros(q.shape, f32)
    kb, vb, bkb = k, v, bias_k
    dk_acc = jnp.zeros(k.shape, f32)
    dv_acc = jnp.zeros(v.shape, f32)
    db_acc = jnp.zeros(bias_k.shape, f32)
    use_flash = _sp_flash_ok(sq, p_drop)
    head_ids = jnp.arange(H)
    for i in range(n):
        src = jnp.mod(r - i, n)

        def hop(dq_, dk_, dv_, db_, kb=kb, vb=vb, bkb=bkb, src=src,
                diag=(causal and i == 0)):
            if use_flash and not diag:
                dqh, dkh, dvh, dbh = _pallas_attention_flash_bwd(
                    q, kb, vb, bkb, seed, do, o, lse, scale, 0.0)
            else:
                s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                               kb.astype(f32)) * scale + bkb
                if diag:
                    s = _diag_causal_mask(s)
                p = jnp.exp(s - lse)          # global softmax, undropped
                pd = p
                if p_drop > 0.0:
                    keep = _sp_dropout_keep(seed, batch_ids, head_ids,
                                            r * (sq // T), src * (sq // T),
                                            sq, sq, p_drop)
                    pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
                dpd = jnp.einsum("bhqd,bhkd->bhqk", do_f, vb.astype(f32))
                dvh = jnp.einsum("bhqk,bhqd->bhkd", pd, do_f)
                ds = pd * dpd - p * dd
                dqh = jnp.einsum("bhqk,bhkd->bhqd", ds,
                                 kb.astype(f32)) * scale
                dkh = jnp.einsum("bhqk,bhqd->bhkd", ds,
                                 q.astype(f32)) * scale
                dbh = jnp.sum(ds, axis=(1, 2), keepdims=True)
            return (dq_ + dqh.astype(f32), dk_ + dkh.astype(f32),
                    dv_ + dvh.astype(f32), db_ + dbh.astype(f32))

        if causal and i > 0:
            dq, dk_acc, dv_acc, db_acc = jax.lax.cond(
                src > r, lambda a, b, c, d: (a, b, c, d), hop,
                dq, dk_acc, dv_acc, db_acc)
        else:
            dq, dk_acc, dv_acc, db_acc = hop(dq, dk_acc, dv_acc, db_acc)
        if n > 1:
            # unlike the forward, rotate after EVERY hop: n rotations
            # land each chunk's gradient accumulator back home
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            bkb = jax.lax.ppermute(bkb, axis_name, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
            db_acc = jax.lax.ppermute(db_acc, axis_name, perm)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype), db_acc.astype(bias_k.dtype),
            _seed_ct(seed), _seed_ct(batch_ids))


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def _sp_dropout_attention(q, k, v, bias, scale, p_drop, keep):
    """Full-sequence attention with the shard-invariant tiled dropout
    mask (the Ulysses dropout path; plain autodiff — no custom vjp)."""
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32),
                   k.astype(f32)) * scale + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", pd, v.astype(f32))


def _ulysses_attention(q, k, v, bias_k, seed, batch_ids, axis_name, n,
                       causal, scale, p_drop):
    """Ulysses hop (shard-local view): all_to_all heads<->sequence, full-
    sequence attention over H/n heads via the single-chip dispatch, then
    the inverse all_to_all. Dropout masks key on GLOBAL head ids so the
    sharded run reproduces the single-shard run."""
    B, H, sl, dh = q.shape
    if n > 1:
        qg = jax.lax.all_to_all(q, axis_name, 1, 2, tiled=True)
        kg = jax.lax.all_to_all(k, axis_name, 1, 2, tiled=True)
        vg = jax.lax.all_to_all(v, axis_name, 1, 2, tiled=True)
        bias_g = jax.lax.all_gather(bias_k, axis_name, axis=3, tiled=True)
        r = jax.lax.axis_index(axis_name)
    else:
        qg, kg, vg, bias_g, r = q, k, v, bias_k, jnp.int32(0)
    Hc, S = qg.shape[1], qg.shape[2]
    bias_full = bias_g                               # [B, 1, 1, S]
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        bias_full = bias_g + jnp.where(cols <= rows, 0.0,
                                       -1e30)[None, None]
    if p_drop > 0.0:
        keep = _sp_dropout_keep(seed, batch_ids, r * Hc + jnp.arange(Hc),
                                0, 0, S, S, p_drop)
        og = _sp_dropout_attention(qg, kg, vg, bias_full, scale, p_drop,
                                   keep).astype(q.dtype)
    else:
        og = _fused(qg, kg, vg,
                    jnp.broadcast_to(bias_full,
                                     (B, 1, bias_full.shape[2], S)),
                    scale, 0.0, seed)
    if n > 1:
        og = jax.lax.all_to_all(og, axis_name, 2, 1, tiled=True)
    return og


def _sp_split_heads(x3, n_heads):
    B, S, HD = x3.shape
    return x3.reshape(B, S, n_heads, HD // n_heads).transpose(0, 2, 1, 3)


def _sp_merge_heads(x4):
    B, H, S, dh = x4.shape
    return x4.transpose(0, 2, 1, 3).reshape(B, S, H * dh)


def _sp_local(q3, k3, v3, bias_k, seed, *, strategy, axis_name, batch_axis,
              n, n_heads, causal, scale, p_drop):
    """Shard-local body (also the n=1 single-device path, which is the
    shard-invariance oracle in tests): packed [B, S/n, H*d] in and out —
    the head split/merge stays inside the shard, off the program graph.
    batch_axis names the mesh axis the batch dim is sharded over (None
    when unsharded) — dropout masks key on GLOBAL batch indices."""
    q = _sp_split_heads(q3, n_heads)
    k = _sp_split_heads(k3, n_heads)
    v = _sp_split_heads(v3, n_heads)
    b0 = jnp.int32(0)
    if batch_axis is not None:
        b0 = jax.lax.axis_index(batch_axis) * q.shape[0]
    batch_ids = b0 + jnp.arange(q.shape[0])
    if strategy == "ulysses":
        o = _ulysses_attention(q, k, v, bias_k, seed, batch_ids,
                               axis_name, n, causal, scale, p_drop)
    else:
        o = _ring(q, k, v, bias_k, seed, batch_ids, axis_name, n, causal,
                  scale, p_drop)
    return _sp_merge_heads(o.astype(q3.dtype))


def sequence_parallel_attention(q, k, v, n_heads, bias=None, mesh=None,
                                seq_axis="sp", batch_axis="dp",
                                causal=False, scale=None, dropout_prob=0.0,
                                rng_key=None, strategy="auto"):
    """Multi-head attention with the sequence dim sharded over
    ``mesh[seq_axis]``.

    q/k/v: GLOBAL packed [B, S, H*d] (the fc-native layout — no head
    transposes in the graph); bias: optional row-broadcast [B, 1, 1, S]
    additive (the k-side padding mask; the causal triangle comes from
    ``causal=True``, never from bias). Returns [B, S, H*d].

    strategy: "auto" picks ulysses when the axis size divides H (lower
    comm volume: 2 all_to_alls of activations vs n-1 K/V hops), ring
    otherwise. With ``mesh=None`` (or no seq_axis in it) the same math runs
    single-shard with no collectives.
    """
    B, S, HD = q.shape
    H = int(n_heads)
    if HD % H:
        raise ValueError("model width %d not divisible by n_heads %d"
                         % (HD, H))
    if scale is None:
        scale = 1.0 / math.sqrt(HD // H)
    scale = float(scale)
    p_drop = float(dropout_prob)
    if p_drop > 0.0:
        if rng_key is None:
            raise ValueError("dropout_prob > 0 requires rng_key")
        seed = jax.random.randint(rng_key, (1,), 0, 2 ** 31 - 1,
                                  dtype=jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    if bias is None:
        bias_k = jnp.zeros((B, 1, 1, S), jnp.float32)
    else:
        if bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1:
            raise ValueError(
                "sequence_parallel_attention bias must be row-broadcast "
                "[B, 1, 1, S] (pass causal=True for the causal mask); "
                "got %r" % (bias.shape,))
        bias_k = jnp.broadcast_to(bias.astype(jnp.float32), (B, 1, 1, S))

    n = 1
    if mesh is not None and seq_axis in mesh.shape:
        n = int(mesh.shape[seq_axis])
    if strategy == "auto":
        strategy = "ulysses" if H % n == 0 else "ring"
    if strategy not in ("ring", "ulysses"):
        raise ValueError("strategy %r not understood (ring | ulysses | "
                         "auto)" % (strategy,))
    if strategy == "ulysses" and H % n:
        raise ValueError("ulysses needs the %r axis size (%d) to divide "
                         "n_heads (%d); use strategy='ring'"
                         % (seq_axis, n, H))
    if S % max(n, 1):
        raise ValueError("sequence length %d not divisible by %r axis "
                         "size %d" % (S, seq_axis, n))
    if p_drop > 0.0 and (S // n) % _SP_DROP_TILE:
        raise ValueError(
            "sequence-parallel dropout needs the per-shard chunk "
            "(S/n = %d) divisible by the %d-wide mask tile"
            % (S // n, _SP_DROP_TILE))

    from paddle_tpu.fluid import monitor
    monitor.gauge("attn_seq_shards",
                  "sequence shards in the last traced "
                  "sequence-parallel attention").set(n)
    if strategy == "ring" and n > 1:
        monitor.counter("attn_ring_hops_total",
                        "ring-attention KV rotation hops traced "
                        "(n_shards - 1 per ring pass)").inc(n - 1)

    if n == 1:
        return _sp_local(q, k, v, bias_k, seed, strategy=strategy,
                         axis_name=None, batch_axis=None, n=1, n_heads=H,
                         causal=causal, scale=scale, p_drop=p_drop)
    P = jax.sharding.PartitionSpec
    ba = None
    if batch_axis and batch_axis in mesh.shape:
        if int(mesh.shape[batch_axis]) > 1 and \
                B % int(mesh.shape[batch_axis]) == 0:
            ba = batch_axis
    local = functools.partial(_sp_local, strategy=strategy,
                              axis_name=seq_axis, batch_axis=ba, n=n,
                              n_heads=H, causal=causal, scale=scale,
                              p_drop=p_drop)
    spec = P(ba, seq_axis, None)
    bspec = P(ba, None, None, seq_axis)
    sm = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, bspec, P(None)),
        out_specs=spec, check_vma=False)
    return sm(q, k, v, bias_k, seed)
