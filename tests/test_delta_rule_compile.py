"""The delta rule's kernels, the causal flash attention's, the select
tier's with its selection op and the short convolution's, each at its
benchmark cell's shape, compiled for a DESCRIBED v5e (no chip attached):
what the chip's compiler refuses - a slice off the tiling, too much
VMEM, an op Mosaic cannot lower - it refuses here, at no chip time.
Nothing runs, so nothing is said about results or speed. The topology is
described inside a fixture, never at import: only the worker that is
given this file loads the TPU's library."""

import re

import jax
import jax.numpy as jnp
import pytest

# qwen3-next-80b-a3b.train-s8192: batch, sequence, key heads, value heads,
# head dim, chunk; the attention layer's heads and head dim
B, S, HK, HV, D, C = 2, 8192, 16, 32, 128, 64
HA, DA = 16, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_text(one_chip, monkeypatch):
    """``fn -> HLO text`` of ``fn`` over the cell's q, k, v, gc, beta (or
    over ``shapes``, bfloat16 or ``(shape, dtype)``), compiled for the chip
    with the kernels NOT interpreted."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((B, S, HK * D), jnp.bfloat16),
            spec((B, S, HK * D), jnp.bfloat16),
            spec((B, S, HV * D), jnp.bfloat16),
            spec((B, HV, S // 128, 128), jnp.float32),
            spec((B, HV, S // 128, 128), jnp.float32))
    yield lambda fn, shapes=None: jax.jit(fn).lower(*(
        args if shapes is None else
        [spec(*(s if isinstance(s[0], tuple) else (s, jnp.bfloat16)))
         for s in shapes])).compile().as_text()
    jax.config.update("jax_enable_compilation_cache", True)


def _core(*args):
    from paddle_tpu.kernels import delta_rule

    return delta_rule._core(*args, D, D, C, 1e-6)


def test_forward_kernel_compiles_for_v5e_at_the_cells_shape(compiled_text):
    text = compiled_text(_core)
    assert "tpu_custom_call" in text and "gdn_chunk_fwd" in text


def test_backward_kernel_compiles_for_v5e_at_the_cells_shape(compiled_text):
    text = compiled_text(jax.grad(
        lambda *a: jnp.sum(_core(*a).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))
    assert "tpu_custom_call" in text
    assert "gdn_chunk_fwd" in text and "gdn_chunk_bwd" in text


def test_causal_flash_kernels_compile_for_v5e_at_the_cells_shape(
        compiled_text, monkeypatch):
    """Forward, dq and dk/dv over the causal tiles alone: the
    scalar-prefetched step tables and the diagonal tile's strips pass
    Mosaic."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_supports_pallas", lambda: True)
    text = compiled_text(
        jax.grad(lambda q, k, v: jnp.sum(A.fused_attention(
            q, k, v, scale=DA ** -0.5, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2)), [(B, HA, S, DA)] * 3)
    for name in ("attn_flash_fwd", "attn_flash_bwd_dq", "attn_flash_bwd_dkv"):
        assert name in text


# lfm2-24b-a2b.train-s8192's attention layer: batch, heads (K/V repeated to
# them), head dim
LB, LH, LD = 4, 32, 64


def test_causal_flash_kernels_at_d64_compile_for_v5e_at_the_cells_shape(
        compiled_text, monkeypatch):
    """At d = 64 the diagonal tile's strips slice 64-lane q, k and v
    blocks: they pass Mosaic in all three kernels."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_supports_pallas", lambda: True)
    text = compiled_text(
        jax.grad(lambda q, k, v: jnp.sum(A.fused_attention(
            q, k, v, scale=LD ** -0.5, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2)), [(LB, LH, S, LD)] * 3)
    for name in ("attn_flash_fwd", "attn_flash_bwd_dq", "attn_flash_bwd_dkv"):
        assert name in text
    assert A._diag_edge(A._flash_block(S), LD) < A._flash_block(S)


# keye-vl-2.0-30b-a3b.train-s16384: batch, Q heads, KV heads, sequence, head
# dim; the indexer's heads, head dim and topk
KB, KH, KHKV, KS, KD, KHI, KDI, KTOPK = 1, 32, 4, 16384, 128, 16, 64, 2048


def test_select_kernels_compile_for_v5e_at_the_cells_shape(compiled_text,
                                                           monkeypatch):
    """Forward, dq and dk/dv under a selection: a group of 8 heads a grid
    step at tile 1024 fits the kernels' VMEM limit, and the dynamic head
    index and the int8 selection tile pass Mosaic."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_supports_pallas", lambda: True)
    text = compiled_text(
        jax.grad(lambda q, k, v, sel: jnp.sum(A.fused_attention(
            q, k, v, scale=KD ** -0.5, causal=True,
            select=sel).astype(jnp.float32)), argnums=(0, 1, 2)),
        [(KB, KH, KS, KD), (KB, KHKV, KS, KD), (KB, KHKV, KS, KD),
         ((KB, KS, KS), jnp.int8)])
    for name in ("attn_select_fwd", "attn_select_bwd_dq",
                 "attn_select_bwd_dkv"):
        assert name in text
    assert A._select_block(KS) == 1024
    assert A._select_group(KH, KHKV) == 8


def test_sparse_index_compiles_for_v5e_at_the_cells_shape(compiled_text):
    """The selection of one layer, chunked: what it holds beside its
    [S, S] byte mask stays far under one [S, S] float32 (1.07e9 B)."""
    from paddle_tpu.fluid.ops.sparse_attention import sparse_index_select

    text = compiled_text(
        lambda q, k, w: sparse_index_select(q, k, w, KTOPK, 512),
        [(KB, KHI, KS, KDI), (KB, KS, KDI), (KB, KS, KHI)])
    assert "s8[1,16384,16384]" in text


# kimi-linear-48b-a3b.train-s16384: batch, sequence, heads, head dim, chunk
# of the channel-gated rule; latent attention's heads and its two widths
NB, NS, NH, ND, NC = 1, 16384, 32, 128, 64
NDQK, NDV = 192, 128


def test_channel_gated_kernels_compile_for_v5e_at_the_cells_shape(
        compiled_text):
    """``kda_chunk_fwd`` and ``kda_chunk_bwd``: the levels' masks, the
    f32-exact 0/1 matmuls, the transposed state and the gate's f32 block
    beside q and k fit the kernels' VMEM limit and pass Mosaic."""
    from paddle_tpu.kernels import delta_rule

    text = compiled_text(
        jax.grad(lambda *a: jnp.sum(delta_rule._core(
            *a, ND, ND, NC, 1e-6).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)),
        [(NB, NS, NH * ND)] * 3 + [((NB, NS, NH * ND), jnp.float32),
                                   ((NB, NH, NS // 128, 128), jnp.float32)])
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    assert "gdn_chunk" not in text


def test_flash_kernels_at_two_widths_compile_for_v5e_at_the_cells_shape(
        compiled_text, monkeypatch):
    """192-wide q and k against 128-wide v: a block whose last dim is no
    multiple of 128 lanes passes Mosaic in all three kernels."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_supports_pallas", lambda: True)
    text = compiled_text(
        jax.grad(lambda q, k, v: jnp.sum(A.fused_attention(
            q, k, v, scale=NDQK ** -0.5, causal=True).astype(jnp.float32)),
            argnums=(0, 1, 2)),
        [(NB, NH, NS, NDQK)] * 2 + [(NB, NH, NS, NDV)])
    for name in ("attn_flash_fwd", "attn_flash_bwd_dq", "attn_flash_bwd_dkv"):
        assert name in text
    assert "bf16[1,32,16384,128]" in text       # o and dv at v's own width


@pytest.mark.parametrize("shape", [(B, S, HK * D * 2 + HV * D),
                                   (NB, NS, 3 * NH * ND)])
def test_conv_kernels_compile_for_v5e_at_the_cells_shapes(compiled_text,
                                                          shape):
    """``conv_silu_fwd`` and ``conv_silu_bwd`` over the q | k | v bank of a
    DeltaNet layer ([2, 8192, 8192]) and of a KDA layer ([1, 16384,
    12288]) in bf16: the f32 copies of a (1024, 512) block, the shifted
    loads and the halo's clamped block pass Mosaic inside the default VMEM
    limit. To be run before any chip call that changes a kernel."""
    from paddle_tpu.kernels import causal_conv

    def both(x, w, dy):
        y, vjp = jax.vjp(
            lambda x, w: causal_conv.causal_conv_pallas(x, w, True), x, w)
        return (y,) + vjp(dy)

    text = compiled_text(both, [shape, (shape[-1], 4), shape])
    assert "conv_silu_fwd" in text and "conv_silu_bwd" in text
    assert causal_conv._largest(causal_conv.TILES, shape[1]) == 1024


def test_gated_conv_kernels_compile_for_v5e_at_the_cells_shape(
        compiled_text):
    """``gconv_fwd`` and ``gconv_bwd`` over the unsplit [B | C | x]
    projection of an LFM2 conv layer ([4, 8192, 3 x 2048] bf16, K = 3):
    five BlockSpecs on the one operand at channel block offsets, the
    backward's part axis writing dB, dC and dx into the one dbcx, inside
    the default VMEM limit. Nothing but the filter is sliced outside the
    kernels, and nothing is concatenated."""
    from paddle_tpu.kernels import causal_conv

    def both(bcx, w, dy):
        y, vjp = jax.vjp(causal_conv.gated_conv_pallas, bcx, w)
        return (y,) + vjp(dy)

    shape = (4, 8192, 3 * 2048)
    text = compiled_text(both, [shape, (2048, 3), (4, 8192, 2048)])
    assert "gconv_fwd" in text and "gconv_bwd" in text
    big = re.findall(r"= bf16\[4,8192,\d+\]\S* (?:slice|concatenate)\(",
                     text)
    assert not big, big
