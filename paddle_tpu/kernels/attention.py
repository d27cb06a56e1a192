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

Tier dispatch. ``PADDLE_TPU_ATTN_FORCE`` (read ONLY through
``_attn_force()``) is the single authority that overrides every gate
below; any value outside ``_ATTN_FORCE_VALUES`` raises instead of
silently routing to the default tier.

Training attention, single chip (``fused_attention`` -> ``_fused``):
  S <= 1024  — batch-blocked kernel, full [S, S] score tile in VMEM.
  1024 < S <= 4096 — Q-tiled long kernels (_fwd/_bwd_kernel_long): K/V
      for one (batch, head) live in VMEM (S·d stays small when S²
      doesn't), scores exist only as [Qb, S] tiles; dk/dv accumulate
      across the q-tile grid dim. Measured v5e BERT-base s=2048: 3.1x
      over the blockwise fallback (20k -> 63k tokens/sec), and +1.5%
      over the flash tier (r5 interleaved pairs: 64.3k vs 63.3k,
      spread ±0.4%), so the tier stays. FORCE=flash bypasses it.
  S > 4096 (or FORCE=flash) — flash tier (_flash_*): BOTH q and k are
      tiled, so no VMEM term scales with S². The forward runs online
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
      blockwise scan. The flash tier does only the causal work: a score
      tile wholly above the diagonal (k-tile j > q-tile i; nt(nt-1)/2 of
      nt² a (batch, head)) is skipped in all three kernels. Its steps
      come FIRST in their sweep (``_flash_ktile``; in dk/dv they do by
      the grid's order): the body sits under ``pl.when``, and their block
      index is that of the sweep's first tile (``_flash_specs``), so the
      pipeline fetches that block under the row before and issues no DMA
      for them. Such a tile's probabilities were exp(-1e30 - m) = 0, and
      the tiles computed keep their order: every output is what it was,
      to the bit. ``attn_flash_tiles_total{kind}`` counts a site's tiles
      computed and skipped. The block and long tiers hold all of K a step
      and have no sweep to shorten. v of ANOTHER WIDTH than q and k (dv !=
      d: latent attention's 192-wide queries and keys against 128-wide
      values) is the flash tier's at every S a tile divides, whatever S:
      its three kernels block v, o, do and dv at ``[Tb, dv]`` and q, k, dq
      and dk at ``[Tb, d]`` (``_flash_specs``), the PV, dV and dP products
      run at dv and the score and dq / dk products at d, so nothing is
      padded; the block and long tiers, which hold q, k and v under one
      block shape, are passed over, and where no tile divides S (or off
      the chip) the jnp forms below take two widths as they are. With dv =
      d every spec, kernel and jaxpr is what it was. A call without ``causal`` traces to
      the jaxpr it always did. Fewer KV than Q heads: the
      ``fused_multihead_attention`` op repeats K/V to the Q head count
      before the kernel (``num_kv_heads``).
  ``select`` given ([B, S, S] integer, nonzero = the query may see the
      key: a learned sparse attention's set of keys a query, one for all
      heads of a batch row, no gradient; ``sparse_index`` makes it) —
      select tier (_select_*) under ``causal``, whatever S a 128-row
      tile divides (tile 1024 / 512 / 256 / 128, the largest that
      divides S; no bias, no dropout) with at most 8 query heads a K/V
      head: the flash tier's online softmax and split backward with the
      selection's tile added to each score tile as 0 / -1e30, the causal
      tile skipping kept. A grid step holds a K/V head's H / Hkv query
      heads: the selection's tile and the K/V tile are fetched once a
      step and serve them all, K/V stay at their own head count, dk/dv
      sum the group's heads inside. Elsewhere (not ``causal``, no tile
      divides S, a wider group, off the chip without the interpreter)
      the selection is a [B, 1, S, S] additive bias on the fallback
      below, which is the kernels' oracle. Without ``select`` nothing
      here is reached.

Packed layout (``fused_attention_packed``, FORCE=packed): q/k/v stay in
the fc-native [B, S, H*d] layout with heads handled inside the kernel;
dispatches resident head-pair tier, then chunked, then the fallback.

Decode (``attention_with_cache``): q [B, H, 1, d] against a KV ring
buffer [B, H, C, d].
  C >= 1024 (or FORCE=decode) — Pallas decode tier
      (_decode_fwd_kernel): online softmax over cache blocks with the
      per-sequence valid length in SMEM. Inference-only, no backward.
  fallback — masked-length one-pass reference (_ref_attention_cache).

Paged decode (``paged_attention_cache``): the KV cache is a SHARED pool
[P, H, ptok, d] indexed by per-slot page tables [B, npages]
(PagedAttention layout; ``paged_kv_cache_update`` is the block-granular
scatter).
  capacity >= 1024 (or FORCE=paged) — Pallas paged tier
      (_paged_decode_fwd_kernel): the decode kernel's online softmax
      with the page table + lengths as SMEM scalar-prefetch operands —
      each K/V block DMAs straight from the pool row the table names,
      so no dense [B, H, C, d] gather ever materializes.
  fallback — gather pages dense (``gather_paged_cache``), then the same
      masked-length reference: bit-identical to the dense ring path.

Sequence-parallel (``sequence_parallel_attention``): S sharded over a
mesh axis, selected per call (strategy attr / auto) with FORCE=ring |
ulysses as the escape hatch.
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

There is also a PACKED entry (``fused_attention_packed``): q/k/v in the
fc-native [B, S, H*d] layout with heads handled inside the kernel,
eliminating the head transposes from the graph. It dispatches to the
RESIDENT head-pair tier (r5; see the resident section below) with the
r4 chunked kernel as fallback. Honest status from v5e measurement at
BERT-base b=128/s=128 — every in-kernel design loses to XLA's
batched-GEMM chain end-to-end:
  einsum chain 87-89 ms | resident 122 ms | per-head fused 126 ms |
  packed-chunked 157 ms; ablation puts the attention core at ~16 ms of
  the 88 ms step, so the chain leaves little on the table that kernel
  relayout/latency costs don't eat.
They are kept as correct, tested building blocks for shapes with
larger S·heads per block; BERT's ``use_fused_attention="auto"`` picks
the GEMM chain below S=256.
"""

import functools
import math
import os

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
    "attn_packed_fwd", "attn_packed_bwd",
    "attn_res_fwd", "attn_res_bwd_dq", "attn_res_bwd_dkv",
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


def _count_flash_tiles(nt, causal, sites=1):
    """Trace-time record, once a traced flash ``pallas_call`` site (of
    which the caller builds ``sites``), of the score tiles a (batch, head)
    that the kernel computes and of those it skips (under ``causal``, the
    ones wholly above the diagonal)."""
    from ..fluid import monitor as _monitor

    computed = nt * (nt + 1) // 2 if causal else nt * nt
    for kind, n in (("computed", computed), ("skipped", nt * nt - computed)):
        _monitor.counter(
            "attn_flash_tiles_total",
            "flash attention score tiles a (batch, head), computed or "
            "skipped as wholly above the causal diagonal (trace-time: "
            "once a traced kernel site, not per step)",
            labels={"kind": kind}).inc(n * sites)


_ATTN_FORCE_VALUES = ("flash", "packed", "decode", "paged", "ring",
                      "ulysses")


def _attn_force():
    """The ONE read site for the PADDLE_TPU_ATTN_FORCE escape hatch.

    Returns "" (no forcing) or one of ``_ATTN_FORCE_VALUES``; any other
    value raises instead of silently routing to the default tier (a typo
    like FORCE=falsh used to measure exactly the path the user was
    trying to bypass)."""
    v = os.environ.get("PADDLE_TPU_ATTN_FORCE", "")
    if v and v not in _ATTN_FORCE_VALUES:
        raise ValueError(
            "PADDLE_TPU_ATTN_FORCE=%r not understood; expected one of "
            "%s (or unset)" % (v, ", ".join(_ATTN_FORCE_VALUES)))
    return v


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
    if _attn_force() == "flash":
        return False        # measurement escape hatch: skip to flash
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


def _flash_ktile(nk, causal):
    """``(j, i)``: the k-tile that this step of a q-tile's sweep computes
    (grid ``(B, H, nq, nk)``, k-tile fastest) and, under ``causal``, the
    q-tile (else None). Read at the kernel's top level: the interpreter
    resolves program ids there only. Under ``causal`` q-tile i has the
    i + 1 tiles 0..i to compute (q and k share the tile edge: a tile with
    j > i lies wholly above the diagonal, its probabilities were
    exp(-1e30 - m) = 0 and it added 0.0 everywhere) and takes them on its
    LAST i + 1 steps, in the same order, so the sweep ends on the diagonal.
    The steps before them have no tile (j < 0) and hold the block of tile
    0, which the pipeline fetched under the row before (``_flash_specs``):
    they move no data."""
    j = pl.program_id(3)
    if not causal:
        return j, None
    i = pl.program_id(2)
    return j - (nk - 1 - i), i


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                      lse_ref, acc_scr, m_scr, l_scr, *, scale, p_drop,
                      n_heads, nq, nk, causal=False):
    """Grid (B, H, nq, nk), k-tile fastest: classic online softmax. The
    (m, l, acc) carries live in VMEM scratch across the k-tile sweep; o
    and the row logsumexp L are written on the last k-tile. Dropout
    masks only the value accumulation — the denominator uses undropped
    weights (same semantics as _blockwise_attention)."""

    j, qi = _flash_ktile(nk, causal)

    def _tile():
        q = q_ref[0, 0]                               # [Tb, d]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, 0]                    # [1, Tb] row-broadcast
        if causal:
            s = _causal_mask(s, qi * q.shape[0], j * k.shape[0])

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, -1e30, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        m_prev = m_scr[...]                           # [Tb, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # [Tb, Tb]
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if p_drop > 0.0:
            b, h, i = (pl.program_id(0), pl.program_id(1),
                       pl.program_id(2))
            pltpu.prng_seed(_flash_seed(seed_ref[0], b, h, i, j,
                                        n_heads, nq, nk))
            u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
            p = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:      # j < 0: a step of the sweep that has no tile
        pl.when(j >= 0)(_tile)
    else:
        _tile()

    @pl.when(j == (qi if causal else nk - 1))
    def _emit():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)       # [Tb, 1]


def _flash_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     lse_ref, dd_ref, dq_ref, dbias_ref, *, scale, p_drop,
                     n_heads, nq, nk, causal=False):
    """Split backward, half 1 — grid (B, H, nq, nk), k-tile fastest: the
    dq block (keyed on the q-tile) accumulates over consecutive k-tile
    steps. Probabilities regenerate from the saved logsumexp: p =
    exp(s - L) is exactly softmax without a second online pass. Also
    emits per-(q-tile) dbias partials, reduced outside the kernel."""

    j, qi = _flash_ktile(nk, causal)

    def _tile():
        q = q_ref[0, 0]                               # [Tb, d]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                           # [Tb, 1]
        dd = dd_ref[0, 0]                             # rowsum(do*o) [Tb, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, 0]
        if causal:
            s = _causal_mask(s, qi * q.shape[0], j * k.shape[0])
        p = jnp.exp(s - lse)                      # undropped softmax rows
        if p_drop > 0.0:
            b, h, i = (pl.program_id(0), pl.program_id(1),
                       pl.program_id(2))
            pltpu.prng_seed(_flash_seed(seed_ref[0], b, h, i, j,
                                        n_heads, nq, nk))
            u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
            pd = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
        else:
            pd = p
        dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = pd * dpd - p * dd                        # [Tb, Tb]
        dbias_ref[0, 0] = jnp.sum(ds, axis=0, keepdims=True)
        contrib = jax.lax.dot_general(
            ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        @pl.when(j == 0)
        def _init():
            dq_ref[0, 0] = contrib

        @pl.when(j != 0)
        def _acc():
            dq_ref[0, 0] += contrib

    if causal:
        pl.when(j >= 0)(_tile)
    else:
        _tile()


def _flash_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                       lse_ref, dd_ref, dk_ref, dv_ref, *, scale, p_drop,
                       n_heads, nq, nk, causal=False):
    """Split backward, half 2 — grid (B, H, nk, nq), q-tile fastest: the
    dk/dv blocks (keyed on the k-tile) accumulate over consecutive
    q-tile steps. The PRNG seed uses the same (i, j) formula as the
    forward, so the regenerated mask is bit-exact despite the
    transposed grid order. Under ``causal`` the sweep's first j steps
    (q-tiles wholly before the k-tile) are skipped, so the first tile
    computed, which initialises dk and dv, is the diagonal one."""

    j, i = pl.program_id(2), pl.program_id(3)
    first = j if causal else 0

    def _tile():
        q = q_ref[0, 0]                               # [Tb, d]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        dd = dd_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, 0]
        if causal:
            s = _causal_mask(s, i * q.shape[0], j * k.shape[0])
        p = jnp.exp(s - lse)
        if p_drop > 0.0:
            b, h = pl.program_id(0), pl.program_id(1)
            pltpu.prng_seed(_flash_seed(seed_ref[0], b, h, i, j,
                                        n_heads, nq, nk))
            u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
            pd = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
        else:
            pd = p
        lp = q.dtype
        dv = jax.lax.dot_general(pd.astype(lp), do,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = pd * dpd - p * dd
        dk = jax.lax.dot_general(ds.astype(lp), q,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale

        @pl.when(i == first)
        def _init():
            dk_ref[0, 0] = dk
            dv_ref[0, 0] = dv

        @pl.when(i != first)
        def _acc():
            dk_ref[0, 0] += dk
            dv_ref[0, 0] += dv

    if causal:
        pl.when(i >= j)(_tile)
    else:
        _tile()


def _flash_specs(q, bias, causal, kfast=True, dv=None):
    """Block specs of the flash kernels' grid ``(B, H, slow, fast)``; the
    last two are the q-tile's and the k-tile's at v's width ``dv`` (o and
    do; v and dv), which are ``qspec`` and ``kspec`` again where v is as
    wide as q and k:
    ``kfast`` has the k-tile fastest (forward, dq), else the q-tile (dk/dv).
    Under ``causal`` the steps of a sweep that have no tile come first
    (``_flash_ktile``; in dk/dv the q-tiles before the k-tile) and name the
    block of the sweep's first tile, so the pipeline fetches it once, under
    the row before, and issues no DMA for them."""
    B, H, S, d = q.shape
    TB = _flash_block(S)
    nt = S // TB
    hb = bias.shape[1]

    def tiles(*g):      # grid indices -> (b, h, q-tile, k-tile) to fetch
        b, h, i, j = g if kfast else (g[0], g[1], g[3], g[2])
        if causal and kfast:
            j = jnp.maximum(j - (nt - 1 - i), 0)
        elif causal:
            i = jnp.maximum(i, j)
        return b, h, i, j

    def spec(block, index):
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


def _pallas_attention_flash(q, k, v, bias, scale, p_drop, seed,
                            causal=False):
    """Returns (o, lse): lse [B, H, S] f32 feeds the split backward."""

    _count_kernel("flash")
    B, H, S, d = q.shape
    dv = v.shape[-1]        # v's own width; q's and k's is d
    TB, nt, qspec, kspec, bspec, rowspec, _, ospec, vspec = _flash_specs(
        q, bias, causal, dv=dv)
    _count_flash_tiles(nt, causal)
    f32 = jnp.float32
    return _kernel_call(
        "attn_flash_fwd",
        functools.partial(_flash_fwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=H, nq=nt, nk=nt, causal=causal),
        grid=(B, H, nt, nt),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, kspec, vspec, bspec],
        out_specs=[ospec, rowspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, 1), f32)],
        scratch_shapes=[pltpu.VMEM((TB, dv), f32),
                        pltpu.VMEM((TB, 1), f32),
                        pltpu.VMEM((TB, 1), f32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(seed, q, k, v, bias)


def _pallas_attention_flash_bwd(q, k, v, bias, seed, do, o, lse, scale,
                                p_drop, causal=False):
    _count_kernel("flash_bwd")
    B, H, S, d = q.shape
    TB, nt, qspec, kspec, bspec, rowspec, dbpspec, ospec, vspec = \
        _flash_specs(q, bias, causal, dv=v.shape[-1])
    _count_flash_tiles(nt, causal, sites=2)     # dq, dk/dv
    f32 = jnp.float32
    dd = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1,
                 keepdims=True)                            # [B, H, S, 1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    dq, dbp = _kernel_call(
        "attn_flash_bwd_dq",
        functools.partial(_flash_dq_kernel, scale=scale, p_drop=p_drop,
                          n_heads=H, nq=nt, nk=nt, causal=causal),
        grid=(B, H, nt, nt),
        in_specs=[smem, qspec, kspec, vspec, bspec, ospec, rowspec,
                  rowspec],
        out_specs=[qspec, dbpspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct((B, H * nt, 1, S), f32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(seed, q, k, v, bias, do, lse, dd)
    # transposed grid: k-tile is the SLOW tile dim so dk/dv accumulate
    # over consecutive q-tile steps
    _, _, qspec_t, kspec_t, bspec_t, rowspec_t, _, ospec_t, vspec_t = \
        _flash_specs(q, bias, causal, kfast=False, dv=v.shape[-1])
    dk, dv = _kernel_call(
        "attn_flash_bwd_dkv",
        functools.partial(_flash_dkdv_kernel, scale=scale, p_drop=p_drop,
                          n_heads=H, nq=nt, nk=nt, causal=causal),
        grid=(B, H, nt, nt),
        in_specs=[smem, qspec_t, kspec_t, vspec_t, bspec_t, ospec_t,
                  rowspec_t, rowspec_t],
        out_specs=[kspec_t, vspec_t],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
    )(seed, q, k, v, bias, do, lse, dd)
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
# (a learned selection picks among the keys before the query): the tiles
# wholly above the diagonal are skipped as in the flash tier
# (``_flash_ktile``), and column > row is masked beside the selection.
# No bias and no dropout (a caller with either gets an error).
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


def _select_neg(sel_ref, neg_scr, qi, j):
    """The step's selection tile as what is added to a score: 0 where the
    query may see the key (selected, and column <= row), else -1e30; made
    once, read by every head."""
    keep = sel_ref[0].astype(jnp.int32) != 0
    tb = keep.shape[0]
    rows = qi * tb + jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0)
    cols = j * tb + jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
    keep = jnp.logical_and(keep, cols <= rows)
    neg_scr[...] = jnp.where(keep, 0.0, -1e30).astype(jnp.float32)


def _select_fwd_kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, lse_ref,
                       neg_scr, acc_scr, m_scr, l_scr, *, scale, G, nk):
    """Grid (B, H/G, nq, nk), k-tile fastest: the flash forward for each
    of the step's G heads in turn. m starts at -1e29, above a masked
    score: a row that has seen no selected key yet keeps p = exp(-1e30 +
    1e29) = 0 and l = 0, and the first selected key's corr = exp(-1e29 -
    m) = 0 starts it."""
    j, qi = _flash_ktile(nk, True)

    @pl.when(j >= 0)
    def _tile():
        _select_neg(sel_ref, neg_scr, qi, j)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, -1e29, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def head(g):
            q, k, v = q_ref[0, g], k_ref[0, 0], v_ref[0, 0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = s + neg_scr[...]
            m_prev = m_scr[g]                             # [Tb, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = m_new

        _for_heads(G, head)

    @pl.when(j == qi)
    def _emit():
        def head(g):
            l = l_scr[g]
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_scr[g] + jnp.log(l)

        _for_heads(G, head)


def _select_dq_kernel(q_ref, k_ref, v_ref, sel_ref, do_ref, lse_ref, dd_ref,
                      dq_ref, neg_scr, *, scale, G, nk):
    """Grid (B, H/G, nq, nk), k-tile fastest: dq of the step's G heads,
    accumulated over the k-tile sweep; p = exp(s - L) from the saved
    logsumexp, 0 where the selection masks."""
    j, qi = _flash_ktile(nk, True)

    @pl.when(j >= 0)
    def _tile():
        _select_neg(sel_ref, neg_scr, qi, j)

        @pl.when(j == 0)
        def _init():
            dq_ref[...] = jnp.zeros(dq_ref.shape, dq_ref.dtype)

        def head(g):
            q, k, v, do = q_ref[0, g], k_ref[0, 0], v_ref[0, 0], do_ref[0, g]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s + neg_scr[...] - lse_ref[0, g])
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * dp - p * dd_ref[0, g]
            dq_ref[0, g] += jax.lax.dot_general(
                ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        _for_heads(G, head)


def _select_dkdv_kernel(q_ref, k_ref, v_ref, sel_ref, do_ref, lse_ref,
                        dd_ref, dk_ref, dv_ref, neg_scr, *, scale, G):
    """Grid (B, Hkv, nk, nq), q-tile fastest: dk and dv of the step's K/V
    head, accumulated over the q-tile sweep and over its G query heads
    (f32, [B, Hkv, S, d]: no repeated K/V to sum back). The sweep's first
    j steps, above the diagonal, are skipped."""
    j, i = pl.program_id(2), pl.program_id(3)

    @pl.when(i >= j)
    def _tile():
        _select_neg(sel_ref, neg_scr, i, j)

        @pl.when(i == j)
        def _init():
            dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
            dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)

        def head(g):
            q, k, v, do = q_ref[0, g], k_ref[0, 0], v_ref[0, 0], do_ref[0, g]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s + neg_scr[...] - lse_ref[0, g])
            lp = q.dtype
            dv_ref[0, 0] += jax.lax.dot_general(
                p.astype(lp), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * dp - p * dd_ref[0, g]
            dk_ref[0, 0] += jax.lax.dot_general(
                ds.astype(lp), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        _for_heads(G, head)


def _select_specs(q, k, kfast=True):
    """Block specs of the select kernels' grid ``(B, Hkv, slow, fast)``,
    with ``_flash_specs``' treatment of the steps that have no tile."""
    B, H, S, d = q.shape
    G = _select_group(H, k.shape[1])
    TB = _select_block(S)
    nt = S // TB

    def tiles(*g):      # grid indices -> (b, K/V head, q-tile, k-tile)
        b, h, i, j = g if kfast else (g[0], g[1], g[3], g[2])
        if kfast:
            j = jnp.maximum(j - (nt - 1 - i), 0)
        else:
            i = jnp.maximum(i, j)
        return b, h, i, j

    def spec(block, index):
        return pl.BlockSpec(block, lambda *g: index(*tiles(*g)))

    qspec = spec((1, G, TB, d), lambda b, h, i, j: (b, h, i, 0))
    kspec = spec((1, 1, TB, d), lambda b, h, i, j: (b, h, j, 0))
    sspec = spec((1, TB, TB), lambda b, h, i, j: (b, i, j))
    rowspec = spec((1, G, TB, 1), lambda b, h, i, j: (b, h, i, 0))
    return G, TB, nt, qspec, kspec, sspec, rowspec


def _pallas_attention_select(q, k, v, select, scale):
    """Returns (o, lse); k, v at their own head count [B, Hkv, S, d]."""
    _count_kernel("select")
    B, H, S, d = q.shape
    G, TB, nt, qspec, kspec, sspec, rowspec = _select_specs(q, k)
    f32 = jnp.float32
    return _kernel_call(
        "attn_select_fwd",
        functools.partial(_select_fwd_kernel, scale=scale, G=G, nk=nt),
        grid=(B, H // G, nt, nt),
        in_specs=[qspec, kspec, kspec, sspec],
        out_specs=[qspec, rowspec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, S, 1), f32)],
        scratch_shapes=[pltpu.VMEM((TB, TB), f32),
                        pltpu.VMEM((G, TB, d), f32),
                        pltpu.VMEM((G, TB, 1), f32),
                        pltpu.VMEM((G, TB, 1), f32)],
        compiler_params=_SELECT_COMPILER_PARAMS,
    )(q, k, v, select)


def _pallas_attention_select_bwd(q, k, v, select, do, o, lse, scale):
    _count_kernel("select")     # dq
    _count_kernel("select")     # dk/dv
    B, H, S, d = q.shape
    G, TB, nt, qspec, kspec, sspec, rowspec = _select_specs(q, k)
    f32 = jnp.float32
    dd = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1, keepdims=True)
    neg = pltpu.VMEM((TB, TB), f32)
    dq = _kernel_call(
        "attn_select_bwd_dq",
        functools.partial(_select_dq_kernel, scale=scale, G=G, nk=nt),
        grid=(B, H // G, nt, nt),
        in_specs=[qspec, kspec, kspec, sspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, f32),
        scratch_shapes=[neg],
        compiler_params=_SELECT_COMPILER_PARAMS,
    )(q, k, v, select, do, lse, dd)
    _, _, _, qspec_t, kspec_t, sspec_t, rowspec_t = _select_specs(
        q, k, kfast=False)
    dk, dv = _kernel_call(
        "attn_select_bwd_dkv",
        functools.partial(_select_dkdv_kernel, scale=scale, G=G),
        grid=(B, H // G, nt, nt),
        in_specs=[qspec_t, kspec_t, kspec_t, sspec_t, qspec_t, rowspec_t,
                  rowspec_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32)],
        scratch_shapes=[neg],
        compiler_params=_SELECT_COMPILER_PARAMS,
    )(q, k, v, select, do, lse, dd)
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


_PACKED_MAX_SEQ = 256  # past this even hc=1 chunks overflow the temp
                       # budget (22 live [S, S] f32 tiles, _packed_hc)


def _packed_hc(n_heads, S):
    """Heads per inner chunk: largest divisor of H whose live
    [hc, S, S] f32 score-family temporaries stay under 8 MB. Measured
    anchor: 12 unchunked heads at S=128 allocated 17.45 MB of kernel
    stack — ~22 live [S, S] f32 tiles per head once Mosaic's scheduler
    is done, hence the 22x coefficient."""
    for hc in range(n_heads, 0, -1):
        if n_heads % hc:
            continue
        if 22 * hc * S * S * 4 <= 8 * 1024 * 1024:
            return hc
    return None


def _packed_bb(B, S, HD, n_heads):
    """Batch block for the packed kernels: largest divisor of B whose
    backward DMA set (7 double-buffered [Bb, S, H, d] bf16 in/out blocks
    + their in-VMEM transposed copies) plus the chunked ~8 MB temp
    budget fits scoped VMEM. The backward bound is used for the forward
    too so the dropout PRNG draw shapes line up (cf. _fwd_budget)."""
    if _packed_hc(n_heads, S) is None:
        return None
    best = None
    for bb in range(1, B + 1):
        if B % bb:
            continue
        est = 42 * bb * S * HD + 8 * 1024 * 1024
        if est <= 15 * 1024 * 1024:
            best = bb
    return best


def _use_packed_kernel(q3, n_heads, p_drop, bias):
    """Packed tier: q/k/v in the fc-native [B, S, H*d] layout, heads
    looped inside the kernel. Kills BOTH failure modes of small-S
    attention: the XLA chain's HBM-materialized [B,H,S,S] probability
    tensors, and the layout copies/transposes the per-head kernel's
    [B,H,S,d] operands force around every custom call."""
    B, S, HD = q3.shape
    if not _supports_pallas() or S > _PACKED_MAX_SEQ:
        return False
    if HD % n_heads or _packed_bb(B, S, HD, n_heads) is None:
        return False
    if bias.shape[2] != 1 or bias.shape[1] not in (1, n_heads):
        return False
    return not (_interpret() and p_drop > 0.0)


def _split_heads_vmem(t):
    """[Bb, S, H, d] -> [Bb*H, S, d] entirely in VMEM — ONE transpose per
    operand (per-head lane slices of a packed [.., H*d] block at d=64
    would trigger a sub-128-lane relayout for every head; splitting the
    lane dim in-kernel is an unsupported Mosaic shape cast, so the 4D
    view is bitcast OUTSIDE the kernel). Heads merge into the single
    batch dim Mosaic's tpu.matmul supports."""
    Bb, S, H, d = t.shape
    return jnp.swapaxes(t, 1, 2).reshape(Bb * H, S, d)


def _merge_heads_vmem(t, n_heads):
    """Inverse of _split_heads_vmem: [Bb*H, S, d] -> [Bb, S, H, d]."""
    BH, S, d = t.shape
    Bb = BH // n_heads
    return jnp.swapaxes(t.reshape(Bb, n_heads, S, d), 1, 2)


def _packed_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                       scale, p_drop, n_heads):
    """Grid (B/Bb,): one step = Bb batches, ALL heads, one multi-batch
    dot over (Bb, H): scores -> softmax -> dropout -> PV with the
    [Bb, H, S, S] tile never leaving VMEM; the head split/merge is an
    in-VMEM relayout, so HBM only ever sees the packed layout."""

    q = _split_heads_vmem(q_ref[...])             # [Bb*H, S, d], b-major
    k = _split_heads_vmem(k_ref[...])
    v = _split_heads_vmem(v_ref[...])
    BH, S, d = q.shape
    H = n_heads
    hc = _packed_hc(H, S)
    i = pl.program_id(0)
    dn = (((2,), (2,)), ((0,), (0,)))
    outs = []
    for ci in range(BH // hc):
        # contiguous (b, head-chunk) rows bound the live [hc, S, S] f32
        # temporaries; leading-dim slices cost no relayout
        b, c = (ci * hc) // H, (ci * hc) % H
        rows = slice(ci * hc, (ci + 1) * hc)
        qc, kc, vc = q[rows], k[rows], v[rows]
        s = jax.lax.dot_general(qc, kc, dn,
                                preferred_element_type=jnp.float32) * scale
        bsl = (bias_ref[b, c:c + hc] if bias_ref.shape[1] > 1
               else bias_ref[b, 0:1])               # [hc|1, 1, S]
        s = s + bsl
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        if p_drop > 0.0:
            pltpu.prng_seed(seed_ref[0] + i * BH + ci)
            u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
            p = jnp.where(u >= p_drop, p / (1.0 - p_drop), 0.0)
        outs.append(jax.lax.dot_general(
            p.astype(vc.dtype), vc, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))
    o = jnp.concatenate(outs, axis=0)
    o_ref[...] = _merge_heads_vmem(o, n_heads).astype(o_ref.dtype)


def _packed_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                       dq_ref, dk_ref, dv_ref, dbias_ref, *, scale, p_drop,
                       n_heads):
    q = _split_heads_vmem(q_ref[...])             # [Bb*H, S, d], b-major
    k = _split_heads_vmem(k_ref[...])
    v = _split_heads_vmem(v_ref[...])
    do = _split_heads_vmem(do_ref[...])
    BH, S, d = q.shape
    H = n_heads
    Bb = BH // H
    hc = _packed_hc(H, S)
    i = pl.program_id(0)
    per_head_bias = dbias_ref.shape[1] == n_heads
    dn = (((2,), (2,)), ((0,), (0,)))
    lp = q.dtype
    dqs, dks, dvs, dbs = [], [], [], []
    for ci in range(BH // hc):
        b, c = (ci * hc) // H, (ci * hc) % H
        rows = slice(ci * hc, (ci + 1) * hc)
        qc, kc, vc, doc = q[rows], k[rows], v[rows], do[rows]
        s = jax.lax.dot_general(qc, kc, dn,
                                preferred_element_type=jnp.float32) * scale
        bsl = (bias_ref[b, c:c + hc] if bias_ref.shape[1] > 1
               else bias_ref[b, 0:1])
        s = s + bsl
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        if p_drop > 0.0:
            pltpu.prng_seed(seed_ref[0] + i * BH + ci)  # fwd's stream
            u = _uniform_from_bits(pltpu.prng_random_bits(p.shape))
            keep = u >= p_drop
            pd = jnp.where(keep, p / (1.0 - p_drop), 0.0)
        else:
            keep = None
            pd = p
        dv_ = jax.lax.dot_general(pd.astype(lp), doc,
                                  (((1,), (1,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        dpd = jax.lax.dot_general(doc, vc, (((2,), (2,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        dp = dpd if keep is None else jnp.where(keep, dpd / (1.0 - p_drop),
                                                0.0)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        ds_lp = ds.astype(lp)
        dqs.append(jax.lax.dot_general(
            ds_lp, kc, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale)
        dks.append(jax.lax.dot_general(
            ds_lp, qc, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale)
        dvs.append(dv_)
        dbs.append(jnp.sum(ds, axis=1))           # [hc, S]
    dq = jnp.concatenate(dqs, axis=0)
    dk = jnp.concatenate(dks, axis=0)
    dv = jnp.concatenate(dvs, axis=0)
    dq_ref[...] = _merge_heads_vmem(dq, n_heads).astype(dq_ref.dtype)
    dk_ref[...] = _merge_heads_vmem(dk, n_heads).astype(dk_ref.dtype)
    dv_ref[...] = _merge_heads_vmem(dv, n_heads).astype(dv_ref.dtype)
    dsb = jnp.concatenate(dbs, axis=0).reshape(Bb, H, 1, S)
    if per_head_bias:
        dbias_ref[...] = dsb                      # [Bb, H, 1, S]
    else:
        dbias_ref[...] = jnp.sum(dsb, axis=1, keepdims=True)


def _packed_specs4(B, S, H, d, bias, Bb):
    # q/k/v ride as 4D [B, S, H, d] bitcast views (free outside the
    # kernel): block minor dims (H, d) equal the array dims, satisfying
    # the TPU block-shape rule, and the kernel's head transpose happens
    # once per operand in VMEM
    qspec = pl.BlockSpec((Bb, S, H, d), lambda i: (i, 0, 0, 0))
    bspec = pl.BlockSpec((Bb, bias.shape[1], 1, S), lambda i: (i, 0, 0, 0))
    return qspec, bspec


def _pallas_attention_packed(q3, k3, v3, bias, scale, p_drop, seed,
                             n_heads):
    B, S, HD = q3.shape
    d = HD // n_heads
    Bb = _packed_bb(B, S, HD, n_heads)
    qspec, bspec = _packed_specs4(B, S, n_heads, d, bias, Bb)
    v4 = lambda t: t.reshape(B, S, n_heads, d)
    o4 = _kernel_call(
        "attn_packed_fwd",
        functools.partial(_packed_fwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=n_heads),
        grid=(B // Bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, qspec, qspec, bspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, S, n_heads, d), q3.dtype),
    )(seed, v4(q3), v4(k3), v4(v3), bias)
    return o4.reshape(B, S, HD)


def _pallas_attention_packed_bwd(q3, k3, v3, bias, seed, do, scale,
                                 p_drop, n_heads):
    B, S, HD = q3.shape
    d = HD // n_heads
    Bb = _packed_bb(B, S, HD, n_heads)
    qspec, bspec = _packed_specs4(B, S, n_heads, d, bias, Bb)
    dbias_shape = (B, bias.shape[1], 1, S)
    v4 = lambda t: t.reshape(B, S, n_heads, d)
    shape4 = jax.ShapeDtypeStruct((B, S, n_heads, d), q3.dtype)
    dq, dk, dv, dbias = _kernel_call(
        "attn_packed_bwd",
        functools.partial(_packed_bwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=n_heads),
        grid=(B // Bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, qspec, qspec, bspec, qspec],
        out_specs=[qspec, qspec, qspec, bspec],
        out_shape=[shape4, shape4, shape4,
                   jax.ShapeDtypeStruct(dbias_shape, jnp.float32)],
    )(seed, v4(q3), v4(k3), v4(v3), bias, v4(do))
    return (dq.reshape(B, S, HD), dk.reshape(B, S, HD),
            dv.reshape(B, S, HD), dbias)


# -- RESIDENT tier: fc-native operands, per-(batch-block, head) grid ----
#
# Same batched-dot math as the fused tier (_attn_block_fwd/_bwd), but
# the operands keep the layout the QKV projections produce: blocks span
# ALL heads with an index map CONSTANT in the head grid dim, so each
# q/k/v block DMAs once per batch block and is revisited across the H
# head steps; the kernel extracts head h with a dynamic slice in VMEM.
# No [B, H, S, d] relayout in the graph (the per-head tier's 16.5 ms of
# copies) and no in-VMEM swapaxes + per-chunk python loop (the packed
# tier's latency trap). The backward splits into a dq/dbias kernel and
# a dk/dv kernel so each call's revisited in/out blocks fit VMEM.
#
# Mosaic constraints force the HEAD-PAIR design (measured on v5e):
# dynamic lane offsets must be provable multiples of 128 and dynamic
# sublane offsets multiples of 8, so neither a [B, S, H, d] view with a
# dynamic head index (also NOT a free bitcast — Mosaic pads (H, d) =
# (12, 64) to (16, 128)) nor a d=64-wide dynamic lane slice compiles.
# A PAIR of heads is a 2d=128-wide dynamic lane slice (hp*128 —
# provably aligned); the two 64-lane halves split with STATIC slices,
# which Mosaic supports as an in-VMEM relayout. One grid step therefore
# computes two heads.


def _res_bb(B, S, HD, itemsize, n_io, n_live):
    """Largest divisor of B whose revisited IO blocks (double-buffered
    [Bb, S, HD] in the operand dtype) plus live f32 [Bb, S, S]
    score-family temporaries stay inside the 13 MB acceptance bound
    (16 MB scoped VMEM minus headroom; same bound the long tier uses)."""
    best = None
    for bb in range(1, B + 1):
        if B % bb:
            continue
        est = n_io * bb * S * HD * itemsize * 2 + n_live * bb * S * S * 4
        if est <= 13 * 1024 * 1024:
            best = bb
    return best


def _res_blocks(B, S, HD, itemsize):
    # ONE block size for every resident kernel: the dropout PRNG draw
    # shape [Bb, S, S] per (b, h) stream must match between the forward
    # and both backward kernels (the dk/dv call is the tightest: 6 io
    # blocks, ~10 live tiles)
    return _res_bb(B, S, HD, itemsize, n_io=6, n_live=10)


def _use_res_kernel(q3, n_heads, p_drop, bias):
    B, S, HD = q3.shape
    if not _supports_pallas() or S > _MAX_FUSED_SEQ:
        return False
    if _attn_force() == "packed":
        return False        # measurement/bypass hatch: old packed tier
    d = HD // n_heads
    # head pairs: 2d must hit the 128-lane alignment Mosaic can prove
    if HD % n_heads or n_heads % 2 or (2 * d) % 128:
        return False
    if _res_blocks(B, S, HD, jnp.dtype(q3.dtype).itemsize) is None:
        return False
    if bias.shape[2] != 1 or bias.shape[1] not in (1, n_heads):
        return False
    return not (_interpret() and p_drop > 0.0)


def _res_pair(ref, hp, d):
    """Load the 128-lane-aligned head PAIR ``hp`` and split it into two
    [Bb, S, d] halves (static sub-128 slices relayout in VMEM)."""

    pair = ref[:, :, pl.dslice(hp * 2 * d, 2 * d)]
    return pair[:, :, :d], pair[:, :, d:]


def _res_put_pair(ref, hp, d, a, b):
    ref[:, :, pl.dslice(hp * 2 * d, 2 * d)] = jnp.concatenate(
        [a, b], axis=-1)


def _res_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, *,
                    scale, p_drop, n_heads, d):
    b, hp = pl.program_id(0), pl.program_id(1)
    qs = _res_pair(q_ref, hp, d)
    ks = _res_pair(k_ref, hp, d)
    vs = _res_pair(v_ref, hp, d)
    outs = []
    for j in (0, 1):
        bias_b = _res_bias(bias_ref, j)
        o = _attn_block_fwd(qs[j], ks[j], vs[j], bias_b, seed_ref,
                            scale, p_drop, b * n_heads + hp * 2 + j)
        outs.append(o.astype(o_ref.dtype))
    _res_put_pair(o_ref, hp, d, outs[0], outs[1])


def _res_bias(bias_ref, j):
    # broadcast bias blocks are (Bb, 1, 1, S); per-head blocks carry the
    # PAIR (Bb, 2, 1, S) and half j selects its head's row
    if bias_ref.shape[1] == 2:
        return bias_ref[:, j]
    return bias_ref[:, 0]


def _res_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                   dq_ref, dbias_ref, *, scale, p_drop, n_heads, d,
                   acc_heads):
    b, hp = pl.program_id(0), pl.program_id(1)
    qs = _res_pair(q_ref, hp, d)
    ks = _res_pair(k_ref, hp, d)
    vs = _res_pair(v_ref, hp, d)
    dos = _res_pair(do_ref, hp, d)
    dqs, contribs = [], []
    for j in (0, 1):
        dq, _, _, ds = _attn_block_bwd(
            qs[j], ks[j], vs[j], dos[j], _res_bias(bias_ref, j),
            seed_ref, scale, p_drop, b * n_heads + hp * 2 + j)
        dqs.append(dq.astype(dq_ref.dtype))
        contribs.append(jnp.sum(ds, axis=1, keepdims=True))  # [Bb, 1, S]
    _res_put_pair(dq_ref, hp, d, dqs[0], dqs[1])
    if acc_heads:
        both = contribs[0] + contribs[1]

        @pl.when(hp == 0)
        def _init():
            dbias_ref[:, 0] = both

        @pl.when(hp != 0)
        def _acc():
            dbias_ref[:, 0] += both
    else:
        dbias_ref[:, 0] = contribs[0]
        dbias_ref[:, 1] = contribs[1]


def _res_dkdv_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                     dk_ref, dv_ref, *, scale, p_drop, n_heads, d):
    b, hp = pl.program_id(0), pl.program_id(1)
    qs = _res_pair(q_ref, hp, d)
    ks = _res_pair(k_ref, hp, d)
    vs = _res_pair(v_ref, hp, d)
    dos = _res_pair(do_ref, hp, d)
    dks, dvs = [], []
    for j in (0, 1):
        _, dk, dv, _ = _attn_block_bwd(
            qs[j], ks[j], vs[j], dos[j], _res_bias(bias_ref, j),
            seed_ref, scale, p_drop, b * n_heads + hp * 2 + j)
        dks.append(dk.astype(dk_ref.dtype))
        dvs.append(dv.astype(dv_ref.dtype))
    _res_put_pair(dk_ref, hp, d, dks[0], dks[1])
    _res_put_pair(dv_ref, hp, d, dvs[0], dvs[1])


def _res_specs(q3, n_heads, bias):
    B, S, HD = q3.shape
    d = HD // n_heads
    Bb = _res_blocks(B, S, HD, jnp.dtype(q3.dtype).itemsize)
    grid = (B // Bb, n_heads // 2)
    qspec = pl.BlockSpec((Bb, S, HD), lambda b, hp: (b, 0, 0))
    per_head = bias.shape[1] > 1
    bspec = pl.BlockSpec((Bb, 2 if per_head else 1, 1, S),
                         lambda b, hp, _ph=per_head:
                         (b, hp if _ph else 0, 0, 0))
    return grid, qspec, bspec, d


def _pallas_attention_res(q3, k3, v3, bias, scale, p_drop, seed, n_heads):
    grid, qspec, bspec, d = _res_specs(q3, n_heads, bias)
    return _kernel_call(
        "attn_res_fwd",
        functools.partial(_res_fwd_kernel, scale=scale, p_drop=p_drop,
                          n_heads=n_heads, d=d),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  qspec, qspec, qspec, bspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
    )(seed, q3, k3, v3, bias)


def _pallas_attention_res_bwd(q3, k3, v3, bias, seed, do, scale, p_drop,
                              n_heads):
    B, S, HD = q3.shape
    grid, qspec, bspec, d = _res_specs(q3, n_heads, bias)
    acc_heads = bias.shape[1] == 1
    dbias_shape = (B, bias.shape[1], 1, S)
    ops = (seed, q3, k3, v3, bias, do)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                qspec, qspec, qspec, bspec, qspec]
    dq, dbias = _kernel_call(
        "attn_res_bwd_dq",
        functools.partial(_res_dq_kernel, scale=scale, p_drop=p_drop,
                          n_heads=n_heads, d=d, acc_heads=acc_heads),
        grid=grid,
        in_specs=in_specs,
        out_specs=[qspec, bspec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(dbias_shape, jnp.float32)],
    )(*ops)
    dk, dv = _kernel_call(
        "attn_res_bwd_dkv",
        functools.partial(_res_dkdv_kernel, scale=scale, p_drop=p_drop,
                          n_heads=n_heads, d=d),
        grid=grid,
        in_specs=in_specs,
        out_specs=[qspec, qspec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(q3.shape, q3.dtype)],
    )(*ops)
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _packed(q3, k3, v3, bias, scale, p_drop, n_heads, seed):
    if _use_res_kernel(q3, n_heads, p_drop, bias):
        return _pallas_attention_res(q3, k3, v3, bias, scale, p_drop,
                                     seed, n_heads)
    if _use_packed_kernel(q3, n_heads, p_drop, bias):
        return _pallas_attention_packed(q3, k3, v3, bias, scale, p_drop,
                                        seed, n_heads)
    return _packed_fallback(q3, k3, v3, bias, scale, p_drop, n_heads, seed)


def _packed_fallback(q3, k3, v3, bias, scale, p_drop, n_heads, seed):
    """Reshape/transpose into [B, H, S, d] and ride the per-head dispatch
    (which itself falls back to jnp off-TPU)."""
    B, S, HD = q3.shape
    d = HD // n_heads

    def split(t):
        return jnp.transpose(t.reshape(B, S, n_heads, d), (0, 2, 1, 3))

    o = _fused(split(q3), split(k3), split(v3), bias, scale, p_drop, seed)
    return jnp.transpose(o, (0, 2, 1, 3)).reshape(B, S, HD)


def _packed_fwd(q3, k3, v3, bias, scale, p_drop, n_heads, seed):
    return (_packed(q3, k3, v3, bias, scale, p_drop, n_heads, seed),
            (q3, k3, v3, bias, seed))


def _packed_bwd(scale, p_drop, n_heads, res, do):
    q3, k3, v3, bias, seed = res
    if _use_res_kernel(q3, n_heads, p_drop, bias):
        dq, dk, dv, dbias = _pallas_attention_res_bwd(
            q3, k3, v3, bias, seed, do, scale, p_drop, n_heads)
        return dq, dk, dv, dbias.astype(bias.dtype), _seed_ct(seed)
    if _use_packed_kernel(q3, n_heads, p_drop, bias):
        dq, dk, dv, dbias = _pallas_attention_packed_bwd(
            q3, k3, v3, bias, seed, do, scale, p_drop, n_heads)
        return dq, dk, dv, dbias.astype(bias.dtype), _seed_ct(seed)

    def f(q_, k_, v_, b_):
        return _packed_fallback(q_, k_, v_, b_, scale, p_drop, n_heads,
                                seed)

    _, vjp = jax.vjp(f, q3, k3, v3, bias)
    dq, dk, dv, dbias = vjp(do)
    return dq, dk, dv, dbias, _seed_ct(seed)


_packed.defvjp(_packed_fwd, _packed_bwd)


def fused_attention_packed(q, k, v, bias=None, n_heads=1, scale=None,
                           dropout_prob=0.0, rng_key=None):
    """Multi-head attention on PACKED [B, S, H*d] q/k/v (the layout the
    QKV projections produce) — no head split/merge transposes in the
    graph; the kernel strides over head slices in VMEM. bias
    broadcastable [B, 1|H, 1, S] additive; returns [B, S, H*d]."""
    B, S, HD = q.shape
    d = HD // n_heads
    scale, bias, seed = _prep_bias_seed(B, S, d, bias, scale,
                                        dropout_prob, rng_key)
    return _packed(q, k, v, bias, scale, float(dropout_prob),
                   int(n_heads), seed)


def _prep_bias_seed(B, S, d, bias, scale, dropout_prob, rng_key):
    """Shared entry-point epilogue for fused_attention and
    fused_attention_packed: default scale, f32 bias broadcast to the
    batch, and the int32 dropout seed derived from rng_key — factored so
    the two wrappers' dropout streams cannot drift apart."""
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
    return float(scale), bias, seed


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
    scale, bias, seed = _prep_bias_seed(B, S, d, bias, scale,
                                        dropout_prob, rng_key)
    return _fused(q, k, v, bias, scale, float(dropout_prob), seed,
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
    the S>=1024 regime where the Pallas tiers win, with
    PADDLE_TPU_ATTN_FORCE=decode as the escape hatch that forces the
    kernel at any capacity (tests run it on CPU under interpret)."""
    if not _supports_pallas():
        return False
    if _attn_force() == "decode":
        return True
    return k_cache.shape[2] >= _MAX_FUSED_SEQ


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
        # odd/prime capacity (forced-kernel case): pad the cache to the
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
    regime (or FORCE=paged), gated on Pallas availability. The kernel
    additionally needs the page size to tile the lane/sublane rules
    (interpret mode is exempt, like every other tier)."""
    if not _supports_pallas():
        return False
    if _attn_force() == "paged":
        return True
    return page_table.shape[1] * ptok >= _MAX_FUSED_SEQ


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
    otherwise; PADDLE_TPU_ATTN_FORCE=ring|ulysses overrides everything.
    With ``mesh=None`` (or no seq_axis in it) the same math runs
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
    force = _attn_force()
    if force in ("ring", "ulysses"):
        strategy = force
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
