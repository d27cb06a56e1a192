"""The short causal convolution of a linear-attention layer and its SiLU
as one Pallas kernel pair (Gated DeltaNet's and Kimi Delta Attention's
q / k / v filter bank: a K-tap depthwise filter along the sequence, then
``z * sigmoid(z)``).

``x`` [B, S, C] comes as the projection left it, C on lanes, and ``w``
[C, K]. With ``z[t] = sum_j w[:, j] x[t - (K-1) + j]`` (zeros before the
start) and ``y = act(z)``::

    dz = dy * act'(z)              act'(z) = s (1 + z (1 - s)), s = sigmoid(z)
    dx[t] = sum_i w[:, K-1-i] dz[t + i]          (zeros after the end)
    dw[:, j] = sum_{b, t} dz[t] x[t - (K-1) + j]

Each pass reads and writes every array ONCE: the forward reads x and
writes y; the backward reads x and dy, makes z again from x (it is never
stored) and writes dx, with dw accumulated in an f32 block that stays in
VMEM along the batch and the sequence. Arithmetic is f32 whatever the
dtype, the result rounded once.

A grid step owns a (batch, ``LANES`` channels, ``tile`` positions) block
and works through it 128 channels at a time, ``ROWS`` positions a chunk,
so that a chunk's sums, its sigmoid and its products live in vector
registers - in two loops of the kernel, so that the body is traced,
lowered and compiled once (unrolled, the two kernels were 2,100 equations,
3-6% faster alone and 5 s of every process's set-up: chip runs, PR 35). A chunk is put into an f32 VMEM scratch behind the ``PAD`` rows
before it - the chunk before's last rows; before a tile's first chunk the
previous tile's, fetched by a second ``BlockSpec`` on x (``HALO`` rows,
the index clamped; zeros at the start) - and tap j is a LOAD of that
scratch j rows further on: a shifted load costs the vector units nothing,
shifting a register value does (0.99 ms a forward call at [2, 8192, 8192]
bf16 against 1.25: chip runs, PR 35). The backward walks the sequence
from its END, tile by tile and chunk by chunk: dz goes to a second
scratch with ``PAD`` rows after it, the first rows of the dz made just
before (the next chunk's; across tiles carried in VMEM; zeros at the end).

Dispatch (``supported``) is by what the input shows: C a multiple of 128,
a tile that divides S, K - 1 <= 8, bf16 or f32, and a TPU or the
interpreter; ``fluid/ops/linear_attention.py`` holds the XLA form of the
same equations, which is the oracle.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret, named_pallas_call, supports_pallas

KERNEL_NAMES = ("conv_silu_fwd", "conv_silu_bwd")

TILES = (1024, 512, 256)    # positions a grid step, the largest that divides S
LANES = (512, 256, 128)     # channels a grid step, the largest that divides C
ROWS = 128                  # positions a chunk
HALO = 16                   # rows of the halo block: a bf16 tile's sublanes
PAD = 8                     # rows kept from it: an f32 tile's sublanes
_F32 = jnp.float32


def _largest(sizes, n):
    return next((s for s in sizes if n % s == 0), None)


def supported(x_shape, w_shape, dtype):
    """Whether the kernels take this input (see the module's header)."""
    return (len(x_shape) == 3 and x_shape[2] % 128 == 0
            and _largest(TILES, x_shape[1]) is not None
            and 1 <= w_shape[1] - 1 <= PAD
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and supports_pallas())


def _cols(c):
    """The block's c-th 128 channels."""
    return pl.ds(pl.multiple_of(c * 128, 128), 128)


def _rows_before(halo_ref, cols, first):
    """The ``PAD`` rows before the block: the halo's last rows, or zeros
    before the sequence's first tile."""
    return jnp.where(first, 0.0,
                     halo_ref[0, HALO - PAD:, cols].astype(_F32))


def _taps(x_ref, xf_ref, rows, cols, K):
    """The chunk's K taps, the chunk put into the f32 scratch behind the
    ``PAD`` rows before it: tap j holds ``x[t - (K-1) + j]`` at row t."""
    n = xf_ref.shape[0] - PAD
    xf_ref[PAD:] = x_ref[0, rows, cols].astype(_F32)
    return [xf_ref[PAD - (K - 1) + j:PAD - (K - 1) + j + n]
            for j in range(K)]


def _fwd_kernel(x_ref, halo_ref, w_ref, y_ref, xf_ref, *, K, silu):
    n = xf_ref.shape[0] - PAD               # positions a chunk
    first = pl.program_id(2) == 0

    def columns(c, _):
        cols = _cols(c)
        w = [w_ref[j:j + 1, cols] for j in range(K)]
        xf_ref[:PAD] = _rows_before(halo_ref, cols, first)

        def chunk(i, _):
            rows = pl.ds(pl.multiple_of(i * n, n), n)
            z = sum(wj * xj for wj, xj in zip(
                w, _taps(x_ref, xf_ref, rows, cols, K)))
            if silu:
                z = z * jax.nn.sigmoid(z)
            y_ref[0, rows, cols] = z.astype(y_ref.dtype)
            xf_ref[:PAD] = xf_ref[n:]       # the rows before the next chunk

        lax.fori_loop(0, x_ref.shape[1] // n, chunk, None)

    lax.fori_loop(0, x_ref.shape[2] // 128, columns, None)


def _bwd_kernel(x_ref, halo_ref, w_ref, dy_ref, dx_ref, dw_ref, xf_ref,
                dz_ref, after_ref, *, K, silu):
    n = xf_ref.shape[0] - PAD               # positions a chunk
    chunks = x_ref.shape[1] // n
    # the sequence is walked from its end, tile by tile and chunk by chunk:
    # grid step 0 holds the last tile
    last, first = pl.program_id(2) == 0, \
        pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, last))
    def _zero():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def columns(c, _):
        cols = _cols(c)
        w = [w_ref[j:j + 1, cols] for j in range(K)]
        before_tile = _rows_before(halo_ref, cols, first)
        # the rows after this tile: the first rows of the dz made a grid
        # step ago
        dz_ref[n:] = jnp.where(last, 0.0, after_ref[:, cols])

        def chunk(k, sums):
            i = chunks - 1 - k
            start = pl.multiple_of(i * n, n)
            rows = pl.ds(start, n)
            before = x_ref[0, pl.ds(pl.multiple_of(
                jnp.maximum(start - HALO, 0), HALO), HALO), cols]
            xf_ref[:PAD] = jnp.where(
                i == 0, before_tile, before[HALO - PAD:].astype(_F32))
            taps = _taps(x_ref, xf_ref, rows, cols, K)
            dz = dy_ref[0, rows, cols].astype(_F32)
            if silu:
                z = sum(wj * xj for wj, xj in zip(w, taps))
                s = jax.nn.sigmoid(z)
                dz = dz * (s * (1.0 + z * (1.0 - s)))
            dz_ref[:n] = dz
            dx = sum(w[K - 1 - t] * dz_ref[t:t + n] for t in range(K))
            dx_ref[0, rows, cols] = dx.astype(dx_ref.dtype)
            dz_ref[n:] = dz_ref[:PAD]       # the rows after the chunk before
            return [acc + (dz * xj).reshape(-1, PAD, 128).sum(axis=0)
                    for acc, xj in zip(sums, taps)]

        sums = lax.fori_loop(0, chunks, chunk,
                             [jnp.zeros((PAD, 128), _F32)] * K)
        after_ref[:, cols] = dz_ref[n:]
        for j, acc in enumerate(sums):
            dw_ref[j:j + 1, cols] += jnp.sum(acc, axis=0, keepdims=True)

    lax.fori_loop(0, x_ref.shape[2] // 128, columns, None)


@functools.lru_cache(maxsize=None)
def _calls(B, S, C, K, dtype, silu, interpreted):
    """The ``pallas_call``s of one input signature, built ONCE
    (``delta_rule._calls`` says why); ``interpreted`` is part of the key
    only. The filter goes in and its gradient comes out as ``[PAD, C]``
    f32, a tap a row."""
    del interpreted
    tile, lanes = _largest(TILES, S), _largest(LANES, C)
    tiles = S // tile
    xs = jax.ShapeDtypeStruct((B, S, C), dtype)
    ws = jax.ShapeDtypeStruct((PAD, C), _F32)

    def specs(ids):
        """A tile's block, the ``HALO`` rows before it (before the first
        tile: any rows, read as zeros) and the filter's; ``ids`` takes a
        grid step to its (batch, channel block, tile)."""
        def rows(of):
            def index(*g):
                b, c, t = ids(*g)
                return b, of(t), c
            return index

        return (pl.BlockSpec((1, tile, lanes), rows(lambda t: t)),
                pl.BlockSpec((1, HALO, lanes), rows(
                    lambda t: jnp.maximum(t * (tile // HALO) - 1, 0))),
                pl.BlockSpec((PAD, lanes), lambda *g: (0, ids(*g)[1])))

    x, halo, w = specs(lambda b, c, t: (b, c, t))
    fwd = named_pallas_call(
        "conv_silu_fwd", functools.partial(_fwd_kernel, K=K, silu=silu),
        grid=(B, C // lanes, tiles), in_specs=[x, halo, w], out_specs=x,
        out_shape=xs, scratch_shapes=[pltpu.VMEM((PAD + ROWS, 128), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3))
    # dw stays in VMEM along the batch and the sequence, so the channel
    # blocks are the outer axis; the tiles come last first
    x, halo, w = specs(lambda c, b, t: (b, c, tiles - 1 - t))
    bwd = named_pallas_call(
        "conv_silu_bwd", functools.partial(_bwd_kernel, K=K, silu=silu),
        grid=(C // lanes, B, tiles), in_specs=[x, halo, w, x],
        out_specs=[x, w], out_shape=[xs, ws],
        scratch_shapes=[pltpu.VMEM((PAD + ROWS, 128), _F32),
                        pltpu.VMEM((ROWS + PAD, 128), _F32),
                        pltpu.VMEM((PAD, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")))
    return {"fwd": fwd, "bwd": bwd}


def _call(which, x, w, silu):
    B, S, C = x.shape
    return _calls(B, S, C, w.shape[1], jnp.dtype(x.dtype), silu,
                  interpret())[which]


def _rows_of(w):
    """``w`` [C, K] as the kernels read it: [PAD, C] f32, a tap a row."""
    return jnp.pad(w.astype(_F32).T, ((0, PAD - w.shape[1]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_conv_pallas(x, w, silu):
    """``act(conv(x [B, S, C], w [C, K]))`` by the kernels; ``silu`` says
    whether ``act`` is ``z * sigmoid(z)`` or the identity."""
    return _call("fwd", x, w, silu)(x, x, _rows_of(w))


def _conv_fwd(x, w, silu):
    return causal_conv_pallas(x, w, silu), (x, w)


def _conv_bwd(silu, res, dy):
    from ..fluid.ops import linear_attention

    x, w = res
    linear_attention._count_conv("pallas_bwd")
    dx, dw = _call("bwd", x, w, silu)(x, x, _rows_of(w), dy)
    return dx, dw[:w.shape[1]].T.astype(w.dtype)


causal_conv_pallas.defvjp(_conv_fwd, _conv_bwd)
