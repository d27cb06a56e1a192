"""Keye-VL-2.0's language block through ``fluid.layers`` and
``Executor.run`` against the plain float32 reference
(``benchmark/families/keye_vl2_train.py``, which imports nothing of the
program): the model's loss and every trainable leaf's gradient over three
Adam steps with ``topk`` below the sequence length; ``sparse_index``
against a sort of the reference's scores (short rows, planted ties,
chunks); the ``select`` kernels under the Pallas interpreter against the
masked ``_ref_attention`` and, under an all-ones selection, against the
flash tier; ``rotary_embedding`` with positions; and the shares test - the
parts that 8 expert-parallel ranks compute add up to the uncut layer."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.kernels import attention as A  # noqa: E402

TOY = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000000, rope_scaling={"mrope_section": [2, 3, 3]},
    rms_norm_eps=1e-6,
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
               "q_chunk_size": 16, "topk": 12},
    num_experts=4, num_experts_total=16, expert_offset=4,
    num_experts_per_tok=3, norm_topk_prob=True, moe_intermediate_size=16,
    initializer_range=0.02, embedding_std=1.0, learning_rate=1e-3,
    amp="off")
# 40 positions: rows 0-11 keep every causal key, rows 12-39 select 12
MIX = dict(batch=2, seq_len=40, recompute=False)


def _family():
    import run as harness

    return harness.load_module("families", "keye_vl2_train")


@pytest.fixture(scope="module")
def both_sides():
    """Three Adam steps of the program (through ``Executor.run``) and of
    the reference, from one seed: losses, the first gradient of every
    trainable leaf (the program's read back from Adam's first moment),
    and every leaf after the three steps."""
    import compare

    fam = _family()
    with jax.default_matmul_precision("highest"):
        step = fam.build(TOY, MIX)
        # the step donates its state: the reference draws its own copy
        step.set_params(fam.init_params(TOY, 11))
        params = fam.init_params(TOY, 11)
        feeds = fam.feeds(TOY, MIX, 11, compare.STEPS)
        got = {"loss": []}
        for i, feed in enumerate(feeds):
            got["loss"].append(float(np.asarray(step.run(feed)).ravel()[0]))
            if i == 0:
                got["grad"] = {k: np.asarray(v) / (1.0 - 0.9)
                               for k, v in step.first_moments().items()}
        got["params"] = {k: np.asarray(v) for k, v in step.params().items()}
        got["frozen"] = {k: np.asarray(step.scope.find_var(k))
                         for k in fam.index_shapes(TOY)}
        got["adam_params"] = {
            op.input("Param")[0] for op in step.main.global_block().ops
            if op.type == "adam"}
        from paddle_tpu.fluid import profiler

        got["regions"] = profiler.newest_step_regions()

        loss_fn = fam.reference_loss(TOY, compare.matmul("f32"))
        value_and_grad = jax.jit(jax.value_and_grad(loss_fn))
        opt = fam.optimizer(TOY)
        p = dict(params)
        m = {k: jnp.zeros_like(v) for k, v in p.items()}
        v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
        ref = {"loss": []}
        for i, feed in enumerate(feeds):
            loss, g = value_and_grad(p, feed)
            ref["loss"].append(float(loss))
            if i == 0:
                ref["grad"] = {k: np.asarray(x) for k, x in g.items()}
            t = i + 1
            lr_t = opt["lr"] * np.sqrt(1 - opt["beta2"] ** t) \
                / (1 - opt["beta1"] ** t)
            m = {k: opt["beta1"] * m[k] + (1 - opt["beta1"]) * g[k]
                 for k in p}
            v2 = {k: opt["beta2"] * v2[k] + (1 - opt["beta2"]) * g[k] ** 2
                  for k in p}
            p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v2[k]) + opt["epsilon"])
                 for k in p}
        ref["params"] = {k: np.asarray(x) for k, x in p.items()}
        ref["frozen"] = {k: np.asarray(v) for k, v in fam.index_params(
            TOY, feeds[0]["index_seed"]).items()}
        # the reference with the selection ignored: what a dense model reads
        ref["dense_loss"] = float(fam.reference_loss(
            TOY, compare.matmul("f32"), fault="dense")(params, feeds[0]))
    return got, ref


def test_losses_match_reference_over_three_steps(both_sides):
    got, ref = both_sides
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=2e-5)
    # and the selection is at work: the dense model's loss is another
    assert ref["dense_loss"] != ref["loss"][0]


_KINDS = sorted({k.split("_", 2)[2] if k.startswith("layer_") else k
                 for k in _family().param_shapes(TOY)})


@pytest.mark.parametrize("kind", _KINDS)
def test_every_leafs_gradient_and_update_match_reference(both_sides, kind):
    """Each trainable leaf of this kind, in every layer: the first
    gradient element by element, and the leaf after three Adam steps."""
    got, ref = both_sides
    leaves = [k for k in ref["grad"]
              if k == kind or (k.startswith("layer_")
                               and k.split("_", 2)[2] == kind)]
    assert leaves
    for k in leaves:
        scale = np.abs(ref["grad"][k]).max()
        assert scale > 0, k
        np.testing.assert_allclose(got["grad"][k], ref["grad"][k],
                                   rtol=2e-3, atol=2e-4 * scale, err_msg=k)
        clear = np.abs(ref["grad"][k]) > 1e-3 * scale
        np.testing.assert_allclose(
            got["params"][k][clear], ref["params"][k][clear],
            rtol=1e-3, atol=2e-4, err_msg=k)


def test_indexer_leaves_are_frozen_and_follow_the_seed(both_sides):
    """No Adam state for the indexer; its variables hold what
    ``index_params`` draws from the feed's seed, before and after the
    three steps."""
    got, ref = both_sides
    assert set(got["frozen"]) == set(ref["frozen"]) and got["frozen"]
    assert not set(got["frozen"]) & got["adam_params"]
    for k, v in ref["frozen"].items():     # (jitted there, eager here)
        np.testing.assert_allclose(got["frozen"][k], v, rtol=1e-6,
                                   atol=1e-8, err_msg=k)


def test_new_ops_are_filed_and_counted(both_sides):
    from paddle_tpu.fluid import monitor

    found = set(both_sides[0]["regions"].values())
    assert ("forward", "sparse_index") in found
    for phase in ("forward", "backward"):
        assert (phase, "fused_multihead_attention") in found, phase
    assert monitor.counter("sparse_index_dispatch_total",
                           labels={"impl": "bisect"}).value > 0
    kept, causal = (monitor.counter("attn_select_pairs_total",
                                    labels={"kind": k}).value
                    for k in ("kept", "causal"))
    # a site: 12 * 13 / 2 + 28 * 12 kept of 40 * 41 / 2 causal
    assert kept > 0 and kept * 820 == causal * 414


@pytest.mark.parametrize("embedding_std", [None, 1.0])
def test_startup_draws_the_embedding_as_the_config_says(embedding_std):
    """``embed_tokens`` at ``embedding_std`` where the configuration gives
    one (the cell's: 1), else at ``initializer_range`` as every other
    leaf: what the benchmark runs is what the program's startup gives."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import keye_vl2

    cfg = keye_vl2.KeyeVL2Config.from_dict(
        dict(TOY, vocab_size=512, embedding_std=embedding_std))
    _, startup, _ = keye_vl2.build_train_program(
        cfg, 1, MIX["seq_len"], use_amp=False)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor().run(startup)
        scope = fluid.global_scope()
        embed = np.asarray(scope.find_var("embed_tokens"))
        other = np.asarray(scope.find_var("layer_0_attn_q_w"))
    assert embed.std() == pytest.approx(embedding_std or 0.02, rel=0.05)
    assert other.std() == pytest.approx(0.02, rel=0.1)


def test_bf16_step_with_recomputation_follows_the_reference(both_sides):
    """The cell's own policy at the toy size: AMP bf16 over float32
    masters and checkpoints at the layer boundaries (the selection and
    attention's output kept across them). Three steps' losses within
    bf16's reach of the float32 reference's."""
    import compare

    fam = _family()
    cfg = dict(TOY, amp="bfloat16")
    mix = dict(MIX, recompute=True)
    step = fam.build(cfg, mix)
    step.set_params(fam.init_params(cfg, 11))
    got = [float(np.asarray(step.run(f)).ravel()[0])
           for f in fam.feeds(cfg, mix, 11, compare.STEPS)]
    ops = step.main.global_block().ops
    assert any(op.type == "cast" for op in ops)
    assert any(op.type == "autodiff" and op.attr("checkpoints")
               for op in ops)
    np.testing.assert_allclose(got, both_sides[1]["loss"], rtol=3e-3)


# -- sparse_index against a sort of the scores -------------------------------
def _index_inputs(S, Hi=3, di=8, B=2, distinct_keys=None):
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (B, Hi, S, di))
    k = jax.random.normal(ks[1], (B, S, di))
    if distinct_keys:       # planted ties: keys repeat, so scores do
        k = k[:, jnp.arange(S) % distinct_keys]
    w = jax.random.normal(ks[2], (B, S, Hi))
    return q, k, w


def _sorted_rule(q, k, w, topk):
    """The reference's rule from its own scores: every causal key whose
    score reaches the topk-th largest of its row."""
    s = np.einsum("bhtd,bsd->bhts", np.asarray(q, np.float64),
                  np.asarray(k, np.float64))
    score = np.sum(np.maximum(s, 0) * np.asarray(w, np.float64).transpose(
        0, 2, 1)[..., None], 1).astype(np.float32)
    S = score.shape[-1]
    causal = np.arange(S)[None, :] <= np.arange(S)[:, None]
    score = np.where(causal, score, -np.inf)
    kth = np.sort(score, -1)[..., S - topk][..., None]
    return (causal & (score >= kth)).astype(np.int8), score


@pytest.mark.parametrize("chunk", [64, 16])
@pytest.mark.parametrize("distinct_keys", [None, 5])
def test_sparse_index_against_a_sort(chunk, distinct_keys):
    """Rows shorter than ``topk`` keep every causal key; the others keep
    ``topk``, and under planted equal scores every key tied at the
    boundary; chunked equals unchunked."""
    from paddle_tpu.fluid.ops.sparse_attention import sparse_index_select

    S, topk = 64, 12
    q, k, w = _index_inputs(S, distinct_keys=distinct_keys)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(sparse_index_select(q, k, w, topk, chunk))
    want, score = _sorted_rule(q, k, w, topk)
    counts = got.sum(-1)
    assert got.dtype == np.int8 and got.shape == (2, S, S)
    np.testing.assert_array_equal(counts[:, :topk],
                                  np.broadcast_to(np.arange(1, topk + 1), (2, topk)))
    if distinct_keys is None:
        np.testing.assert_array_equal(got, want)
        # exactly topk wherever the topk-th and the next score differ (a
        # score is exactly 0 where every head's relu is: ties of its own)
        ranked = np.sort(score, -1)[:, topk:]
        untied = ranked[..., S - topk] > ranked[..., S - topk - 1]
        assert untied.mean() > 0.5
        assert (counts[:, topk:][untied] == topk).all()
        assert (counts[:, topk:] >= topk).all()
    else:
        # float64 scores against float32: compare where the boundary is
        # clear of rounding, and see that ties were kept whole
        assert (counts[:, topk:] >= topk).all() and counts.max() > topk
        agree = (got == want).mean()
        assert agree > 0.999, agree
        tied = np.asarray(sparse_index_select(q, k, w, topk, S))
        np.testing.assert_array_equal(got, tied)


@pytest.mark.parametrize("k", [1, 5, 50, 100, 130])
def test_kth_largest_is_the_sorts(k):
    """Bisection on the bits against a sort: signed values, both zeros,
    -inf entries, a row with fewer than k entries above -inf, and k past
    the row's length (-inf: every entry reaches it)."""
    from paddle_tpu.fluid.ops.sparse_attention import kth_largest

    x = jax.random.normal(jax.random.PRNGKey(0), (7, 100))
    x = x.at[0, :50].set(-jnp.inf).at[1, 3].set(0.0).at[1, 4].set(-0.0)
    x = x.at[2].set(jnp.where(jnp.arange(100) < 97, -jnp.inf, x[2]))
    x = x.at[3, :10].set(x[3, 10])                      # ties
    want = np.sort(np.asarray(x), -1)[:, 100 - k] if k <= 100 \
        else np.full(7, -np.inf, np.float32)
    np.testing.assert_array_equal(np.asarray(kth_largest(x, k)), want)


def test_sparse_index_layer_through_the_executor():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    S, topk = 32, 6
    q, k, w = _index_inputs(S)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv = layers.data("q", shape=list(q.shape), append_batch_size=False)
        kv = layers.data("k", shape=list(k.shape), append_batch_size=False)
        wv = layers.data("w", shape=list(w.shape), append_batch_size=False)
        sel = layers.sparse_index(qv, kv, wv, topk, chunk_size=8)
    assert sel.stop_gradient and tuple(sel.shape) == (2, S, S)
    with fluid.scope_guard(fluid.Scope()), \
            jax.default_matmul_precision("highest"):
        (got,) = fluid.Executor().run(
            main, feed={"q": np.asarray(q), "k": np.asarray(k),
                        "w": np.asarray(w)}, fetch_list=[sel])
    np.testing.assert_array_equal(got, _sorted_rule(q, k, w, topk)[0])


# -- the select kernels under the interpreter --------------------------------
def _select_case(monkeypatch, gqa, S=256, d=16, B=2, H=4):
    """4 x 4 tiles of 64: tiles above, on and below the diagonal."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(A, "_SELECT_BLOCK_CANDIDATES", (64,))
    Hkv = 2 if gqa else H
    rng = np.random.RandomState(7 + gqa)
    q = jnp.asarray(0.5 * rng.randn(B, H, S, d), jnp.float32)
    k = jnp.asarray(0.5 * rng.randn(B, Hkv, S, d), jnp.float32)
    v = jnp.asarray(0.5 * rng.randn(B, Hkv, S, d), jnp.float32)
    do = jnp.asarray(rng.randn(B, H, S, d), jnp.float32)
    # a random selection inside the causal triangle, the diagonal kept;
    # whole tiles of a row empty now and then (keys 0-63 barred to row
    # tile 2), so a row's first tiles can hold nothing for it
    keep = rng.rand(B, S, S) < 0.2
    keep[:, 128:192, :64] = False
    keep |= np.eye(S, dtype=bool)[None]
    keep &= np.tril(np.ones((S, S), bool))[None]
    assert A._use_select_kernel(q, k, True)
    return q, k, v, do, jnp.asarray(keep.astype(np.int8))


def _masked_reference(q, k, v, select, scale):
    rep = q.shape[1] // k.shape[1]
    bias = jnp.where(select != 0, 0.0, -1e30)[:, None]
    return A._ref_attention(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                            bias, scale, 0.0, None)


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_select_kernels_match_the_masked_reference(monkeypatch, gqa):
    """Forward, dq, dk, dv of the three kernels (K/V at their own head
    count, a group's heads summed inside dk/dv) against ``_ref_attention``
    under the selection as a bias."""
    q, k, v, do, select = _select_case(monkeypatch, gqa)
    scale = q.shape[-1] ** -0.5
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda *a: A.fused_attention(
            *a, scale=scale, causal=True, select=select), q, k, v)
        want, vjp_ref = jax.vjp(lambda *a: _masked_reference(
            *a, select, scale), q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        for name, a, b in zip(("dq", "dk", "dv"), vjp(do), vjp_ref(do)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                       err_msg=name)


def test_all_ones_selection_equals_the_flash_tier(monkeypatch):
    """With nothing deselected the select tier is the causal flash tier:
    the same outputs and gradients, bit for bit (one tile edge)."""
    q, k, v, do, _ = _select_case(monkeypatch, gqa=False)
    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
    monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
    monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
    ones = jnp.ones((q.shape[0],) + (q.shape[2],) * 2, jnp.int8)
    from paddle_tpu.fluid import monitor

    def tier(t):
        return monitor.counter("attn_kernel_dispatch_total",
                               labels={"tier": t}).value

    before = tier("select"), tier("flash"), tier("flash_bwd")
    got, vjp = jax.vjp(lambda *a: A.fused_attention(
        *a, causal=True, select=ones), q, k, v)
    want, vjp_flash = jax.vjp(lambda *a: A.fused_attention(
        *a, causal=True), q, k, v)
    assert (tier("select"), tier("flash"), tier("flash_bwd")) == (
        before[0] + 1, before[1] + 1, before[2])
    np.testing.assert_array_equal(got, want)
    for name, a, b in zip(("dq", "dk", "dv"), vjp(do), vjp_flash(do)):
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert (tier("select"), tier("flash_bwd")) == (before[0] + 3,
                                                   before[2] + 1)


def test_select_goes_with_neither_bias_nor_dropout(monkeypatch):
    q, k, v, _, select = _select_case(monkeypatch, gqa=True)
    with pytest.raises(NotImplementedError):
        A.fused_attention(q, k, v, bias=jnp.zeros((2, 1, 1, 256)),
                          select=select)
    with pytest.raises(NotImplementedError):
        A.fused_attention(q, k, v, dropout_prob=0.1, select=select,
                          rng_key=jax.random.PRNGKey(0))


def test_select_falls_to_the_masked_form_off_the_kernels(monkeypatch):
    """No 128-row tile divides S = 40: the masked ``_ref_attention``,
    with its gradients, K/V at their own head count."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 4, 40, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 40, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 40, 8), jnp.float32)
    keep = np.tril(rng.rand(40, 40) < 0.4) | np.eye(40, dtype=bool)
    select = jnp.asarray(keep[None].astype(np.int8))
    assert not A._use_select_kernel(q, k, True)
    f = lambda *a: jnp.sum(jnp.sin(A.fused_attention(     # noqa: E731
        *a, causal=True, select=select)))
    g = lambda *a: jnp.sum(jnp.sin(_masked_reference(     # noqa: E731
        *a, select, 8 ** -0.5)))
    for a, b in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                    jax.grad(g, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("heads, kv_heads, causal, takes", [
    (8, 1, True, True), (4, 4, True, True), (8, 1, False, False),
    (16, 1, True, False), (6, 4, True, False)],
    ids=["group8", "mha", "not_causal", "group16", "no_group"])
def test_select_tier_takes_causal_groups_of_at_most_8(monkeypatch, heads,
                                                      kv_heads, causal,
                                                      takes):
    """The kernels hold one K/V head's query heads a step, causal only:
    anything else takes the masked form, whose result is the same."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(heads)
    q = jnp.asarray(rng.randn(1, heads, 128, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, kv_heads, 128, 8), jnp.float32)
    assert bool(A._use_select_kernel(q, k, causal)) is takes
    if heads % kv_heads == 0:
        keep = np.tril(rng.rand(128, 128) < 0.3) | np.eye(128, dtype=bool)
        select = jnp.asarray(keep[None].astype(np.int8))
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                A.fused_attention(q, k, k, causal=causal, select=select),
                _masked_reference(q, k, k, select, 8 ** -0.5),
                rtol=2e-5, atol=2e-6)


# -- rotary_embedding with positions -----------------------------------------
def _rope_program(x, positions=None, sections=None, rotary_dim=None):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    feed = {"x": np.asarray(x)}
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=list(x.shape), append_batch_size=False)
        pv = None
        if positions is not None:
            pv = layers.data("p", shape=list(positions.shape), dtype="int64",
                             append_batch_size=False)
            feed["p"] = np.asarray(positions)
        out = layers.rotary_embedding(xv, rotary_dim, 1e4, positions=pv,
                                      mrope_section=sections)
    with fluid.scope_guard(fluid.Scope()):
        return fluid.Executor().run(main, feed=feed, fetch_list=[out])[0]


def test_rotary_with_three_equal_rows_is_the_plain_op_bit_for_bit():
    B, H, S, d = 2, 3, 24, 16
    x = np.random.RandomState(0).randn(B, H, S, d).astype("float32")
    rows = np.broadcast_to(np.arange(S, dtype="int64"), (B, S))
    plain = _rope_program(x)
    np.testing.assert_array_equal(_rope_program(x, positions=rows), plain)
    np.testing.assert_array_equal(
        _rope_program(x, positions=np.stack([rows] * 3), sections=[2, 3, 3]),
        plain)
    # partial rotary keeps the tail
    part = _rope_program(x, positions=rows, rotary_dim=8)
    np.testing.assert_array_equal(part, _rope_program(x, rotary_dim=8))
    np.testing.assert_array_equal(part[..., 8:], x[..., 8:])


def test_rotary_in_sections_with_distinct_rows_matches_the_reference():
    """Frequency pair f turns by the position row of its section."""
    B, H, S, d, sections = 2, 3, 24, 16, [2, 3, 3]
    rng = np.random.RandomState(1)
    x = rng.randn(B, H, S, d).astype("float32")
    pos = rng.randint(0, 50, (3, B, S)).astype("int64")
    got = _rope_program(x, positions=pos, sections=sections)
    inv = 1.0 / (1e4 ** (np.arange(0, d, 2, dtype=np.float64) / d))
    row = np.repeat(np.arange(3), sections)                 # [d / 2]
    ang = np.take_along_axis(
        pos.transpose(1, 2, 0).astype(np.float64),          # [B, S, 3]
        np.broadcast_to(row, (B, S, d // 2)), axis=2) * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(got - _rope_program(x)).max() > 0.1


# -- the shares test ---------------------------------------------------------
def test_eight_shares_add_up_to_the_uncut_expert_layer():
    """One expert layer of 16 experts, top-3, as 8 ranks hold it (2
    experts each, offsets 0-14): the 8 ranks' routed parts, through the
    model file's own expert call and one ``Executor.run``, add up to the
    plain uncut layer over all 16 experts (no shared expert)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decoder_blocks, keye_vl2

    T, h, f, E, held = 24, 32, 16, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (1, T, h))
    router = jax.random.normal(ks[1], (h, E))
    wg, wu = (0.3 * jax.random.normal(ks[i], (E, h, f)) for i in (2, 3))
    wd = 0.3 * jax.random.normal(ks[4], (E, f, h))

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[1, T, h], append_batch_size=False)
        parts = [decoder_blocks.routed_experts(xv, keye_vl2.KeyeVL2Config(
            hidden_size=h, num_experts=held, num_experts_total=E,
            expert_offset=r * held, num_experts_per_tok=3,
            moe_intermediate_size=f), "rank%d" % r)
            for r in range(E // held)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope), jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        for r in range(E // held):
            sl = slice(r * held, (r + 1) * held)
            scope.set_var("rank%d_router_w" % r, jnp.array(router))
            scope.set_var("rank%d_gate_w" % r, wg[sl])
            scope.set_var("rank%d_up_w" % r, wu[sl])
            scope.set_var("rank%d_down_w" % r, wd[sl])
        outs = exe.run(main, feed={"x": np.asarray(x)}, fetch_list=parts)
        uncut = _uncut_experts(x[0], router, wg, wu, wd, 3)
    assert len(outs) == 8 and all(np.abs(o).max() > 0 for o in outs)
    np.testing.assert_allclose(sum(outs)[0], uncut, rtol=1e-4, atol=1e-5)


def test_expert_walks_chunk_follows_the_expected_load():
    """8 of 16 experts under top-3 hold 1.5 pairs a token at even routing:
    the walk's chunk is the whole number of token counts next above that,
    two (one chunk holds an even load), and the layer's result is what a
    token count's chunk gives."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    T, h, f, E, held = 128, 16, 8, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    x = jax.random.normal(ks[0], (T, h))
    main, startup = fluid.Program(), fluid.Program()
    named = lambda n: fluid.ParamAttr(name=n)       # noqa: E731
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[T, h], append_batch_size=False)
        ids, wts = layers.moe_route(xv, E, 3, param_attr=named("router"))
        outs = [layers.moe_experts(
            xv, ids, wts, held, f, expert_offset=4, gate_attr=named("g"),
            up_attr=named("u"), down_attr=named("d"), experts_total=total)
            for total in (None, E)]
    assert [op.attr("experts_total") for op in main.global_block().ops
            if op.type == "moe_experts"] == [0, E]
    with fluid.scope_guard(fluid.Scope()), \
            jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        a, b = exe.run(main, feed={"x": np.asarray(x)}, fetch_list=outs)
    assert np.abs(a).max() > 0
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _uncut_experts(x, router, wg, wu, wd, top):
    """The reference's expert layer over ALL experts, written out from
    the same equations: softmax router, top-k renormalised, no shared
    expert."""
    prob = jax.nn.softmax(x @ router, -1)
    vals, ids = jax.lax.top_k(prob, top)
    w = vals / jnp.sum(vals, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        col = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        out = out + col[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out
