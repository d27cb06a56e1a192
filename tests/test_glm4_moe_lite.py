"""GLM-4.7-Flash's block (``glm4_moe_lite``) through ``fluid.layers`` and
``Executor.run`` against the plain float32 reference
(``benchmark/families/glm4_moe_lite_train.py``, which imports nothing of
the program): the five-layer block with its multi-token-prediction (MTP)
module - loss, every leaf's gradient and three Adam steps under a planted
router bias; the rotation touches q_r and the ONE k_r all heads share and
nothing else; the MTP term alone (weight 0 gives the plain program's loss,
the last position has no target) and its gradient reaching the
embedding, the head and layer 0; the shares test - 8 expert-parallel
ranks' parts plus the shared expert ONCE add up to the uncut layer; the
module attribution a device trace is read by; the config's published
names; and the cell's CPU rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

os.environ.setdefault("PADDLE_TPU_PALLAS_INTERPRET", "1")

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TOY = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=12,
    kv_lora_rank=16, qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=1000000, rms_norm_eps=1e-5, first_k_dense_replace=1,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
    num_experts_total=16, expert_offset=4, num_experts_per_tok=4,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1.8,
    num_nextn_predict_layers=1, mtp_loss_factor=0.3, initializer_range=0.02,
    learning_rate=1e-3, amp="off")
MIX = dict(batch=2, seq_len=40, recompute=False)
CELL = "glm-4.7-flash.train-s8192"


def _family():
    import run as harness

    return harness.load_module("families", "glm4_moe_lite_train")


def _first_loss_and_moments(cfg, feed, seed=11, mix=MIX):
    """The program's first step from the family's weights: its loss and
    Adam's first moments (the gradient times 1 - beta1)."""
    fam = _family()
    step = fam.build(cfg, mix)
    step.set_params(fam.init_params(cfg, seed))
    loss = float(np.asarray(step.run(feed)).ravel()[0])
    return loss, {k: np.asarray(v) / (1.0 - 0.9)
                  for k, v in step.first_moments().items()}, step


@pytest.fixture(scope="module")
def both_sides():
    """Three Adam steps of the program (through ``Executor.run``) and of
    the reference, from one seed, under a router bias that changes the
    choice in most rows: losses, the first gradient of every leaf (the
    program's read back from Adam's first moment), and every leaf after
    the three steps."""
    import compare

    fam = _family()
    with jax.default_matmul_precision("highest"):
        step = fam.build(TOY, MIX)
        # the step donates its state: the reference draws its own copy
        step.set_params(fam.init_params(TOY, 11))
        params = fam.init_params(TOY, 11)
        bias = 0.05 * np.random.default_rng(3).standard_normal(
            (TOY["num_hidden_layers"] + 1, TOY["num_experts_total"]))
        feeds = [dict(f, router_bias=bias.astype("float32"))
                 for f in fam.feeds(TOY, MIX, 11, compare.STEPS)]
        got = {"loss": [], "step": step}
        for i, feed in enumerate(feeds):
            got["loss"].append(float(np.asarray(step.run(feed)).ravel()[0]))
            if i == 0:
                got["grad"] = {k: np.asarray(v) / (1.0 - 0.9)
                               for k, v in step.first_moments().items()}
        got["params"] = {k: np.asarray(v) for k, v in step.params().items()}

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
                ref["unbiased"] = float(value_and_grad(p, dict(
                    feed, router_bias=0.0 * feed["router_bias"]))[0])
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
    return got, ref


def test_losses_match_reference_over_three_steps(both_sides):
    got, ref = both_sides
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=2e-5)
    # the planted bias changed the routing: it is under test
    assert abs(ref["unbiased"] - ref["loss"][0]) > 1e-6 * ref["loss"][0]


_KINDS = sorted({k.split("_", 2)[2] if k.startswith(("layer_", "mtp_"))
                 else k for k in _family().param_shapes(TOY)})


@pytest.mark.parametrize("kind", _KINDS)
def test_every_leafs_gradient_and_update_match_reference(both_sides, kind):
    """Each leaf of this kind, in every layer that has one (the MTP
    module's too): the first gradient element by element, and the leaf
    after three Adam steps."""
    got, ref = both_sides
    leaves = [k for k in ref["grad"]
              if k == kind or (k.startswith(("layer_", "mtp_"))
                               and k.split("_", 2)[2] == kind)]
    assert leaves
    for k in leaves:
        scale = np.abs(ref["grad"][k]).max()
        assert scale > 0, k
        np.testing.assert_allclose(got["grad"][k], ref["grad"][k],
                                   rtol=2e-3, atol=2e-4 * scale, err_msg=k)
        # Adam's first steps move every element by ~lr whatever the
        # gradient's size, so a near-zero gradient's sign decides: hold
        # the elements whose gradient is not noise
        clear = np.abs(ref["grad"][k]) > 1e-3 * scale
        np.testing.assert_allclose(
            got["params"][k][clear], ref["params"][k][clear],
            rtol=1e-3, atol=2e-4, err_msg=k)


def test_the_layers_the_module_and_the_frozen_router_biases(both_sides):
    """Every layer is latent attention with the low-rank query; layer 0
    has the dense MLP, the others and the MTP module experts beside a
    shared one, whose selection-only bias gets no Adam state; the
    embedding and the head are ONE parameter each, read twice; the MTP's
    cross-entropy runs over S - 1 positions a row."""
    step = both_sides[0]["step"]
    ops = step.main.global_block().ops
    trained = [op.input("Param")[0] for op in ops if op.type == "adam"]
    assert len(trained) == len(set(trained))
    for name in ("layer_0_mla_q_a_w", "layer_0_mla_q_a_norm",
                 "layer_0_mlp_gate_w", "layer_4_moe_shared_down_w",
                 "mtp_0_eh_proj_w", "mtp_0_mla_q_b_w", "mtp_0_moe_router_w",
                 "mtp_0_head_norm"):
        assert name in trained, name
    for name in ["layer_%d_moe_router_bias" % i for i in range(1, 5)] + [
            "mtp_0_moe_router_bias"]:
        assert name not in trained
        assert step.scope.find_var(name).shape == (16,)
    assert "layer_0_moe_router_w" not in trained
    assert sum(op.type == "fused_multihead_attention" for op in ops) == 6
    assert sum(op.type == "lookup_table" and
               op.input("W")[0] == "embed_tokens" for op in ops) == 2
    assert sum(op.type == "mul" and op.input("Y")[0] == "lm_head_w"
               for op in ops) == 2
    ces = [op for op in ops if op.type == "softmax_with_cross_entropy"]
    assert [op.attrs.get("op_namescope") for op in ces] == [None, "mtp"]
    block = step.main.global_block()
    assert [tuple(block.var(op.input("Logits")[0]).shape)[0]
            for op in ces] == [2 * 40, 2 * 39]


# -- the rotation ---------------------------------------------------------------
def _rope(x, theta):
    """Rotate-half over the last axis of ``x`` [B, heads, S, d]."""
    d, S = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(S)[:, None] * inv
    cos, sin = np.cos(ang), np.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def test_the_rotation_touches_q_r_and_the_one_shared_k_r_only():
    """The query and the key that latent attention hands the attention
    op, against numpy from the layer's own weights: q's first 6 channels
    a head are W_qb rms(W_qa x) as they are, its last 4 rotated; k's first
    6 are W_kvb rms(c) as they are, its last 4 the ONE rotated k_r, equal
    in every head. Under ``mla_use_nope`` nothing is rotated."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decoder_blocks, glm4_moe_lite

    B, S, h = 2, 12, 32
    cfg = glm4_moe_lite.Glm4MoeLiteConfig.from_dict(TOY)
    x = np.random.default_rng(0).standard_normal((B, S, h)).astype("float32")

    def run(nope):
        cfg.mla_use_nope = nope
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            xv = layers.data("x", shape=[B, S, h], append_batch_size=False)
            decoder_blocks.latent_attention(xv, cfg, "a")
        attn = [op for op in main.global_block().ops
                if op.type == "fused_multihead_attention"][0]
        ropes = [op for op in main.global_block().ops
                 if op.type == "rotary_embedding"]
        shapes = [tuple(main.global_block().var(op.input("X")[0]).shape)
                  for op in ropes]
        scope = fluid.Scope()
        with fluid.scope_guard(scope), jax.default_matmul_precision("highest"):
            exe = fluid.Executor()
            exe.run(startup)
            q, k = exe.run(main, feed={"x": x},
                           fetch_list=[attn.input("Q")[0], attn.input("K")[0]])
            w = {n: np.asarray(scope.find_var("a_" + n)) for n in
                 ("q_a_w", "q_a_norm", "q_b_w", "kv_a_w", "kv_norm", "kv_b_w")}
        return np.asarray(q), np.asarray(k), w, shapes

    def rms(t, w):
        return t / np.sqrt(np.mean(t * t, -1, keepdims=True) + 1e-5) * w

    q, k, w, shapes = run(nope=False)
    assert shapes == [(B, 4, S, 4), (B, 1, S, 4)]    # q_r a head; k_r once
    qw = (rms(x @ w["q_a_w"], w["q_a_norm"]) @ w["q_b_w"]).reshape(
        B, S, 4, 10).transpose(0, 2, 1, 3)
    kva = x @ w["kv_a_w"]
    kvb = (rms(kva[..., :16], w["kv_norm"]) @ w["kv_b_w"]).reshape(
        B, S, 4, 14).transpose(0, 2, 1, 3)
    k_r = _rope(kva[:, None, :, 16:], 1e6)
    tol = dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(q[..., :6], qw[..., :6], **tol)
    np.testing.assert_allclose(q[..., 6:], _rope(qw[..., 6:], 1e6), **tol)
    np.testing.assert_allclose(k[..., :6], kvb[..., :6], **tol)
    np.testing.assert_allclose(k[..., 6:], np.broadcast_to(k_r, (B, 4, S, 4)),
                               **tol)
    assert not np.allclose(q[..., 6:], qw[..., 6:], atol=1e-3)
    q0, k0, _, shapes = run(nope=True)
    assert shapes == []
    np.testing.assert_allclose(q0, qw, **tol)
    np.testing.assert_allclose(k0[..., 6:], np.broadcast_to(
        kva[:, None, :, 16:], (B, 4, S, 4)), **tol)


# -- the MTP term ----------------------------------------------------------------
@pytest.fixture(scope="module")
def one_feed():
    return _family().feeds(TOY, MIX, 11, 1)[0]


def test_mtp_weight_zero_gives_the_plain_programs_loss(one_feed):
    """With ``mtp_loss_factor`` 0 the loss is the program without the
    module's: the main model's leaves from the same seed, the MTP's absent."""
    with jax.default_matmul_precision("highest"):
        zero, _, _ = _first_loss_and_moments(dict(TOY, mtp_loss_factor=0.0),
                                             one_feed)
        fam = _family()
        plain_cfg = dict(TOY, num_nextn_predict_layers=0)
        step = fam.build(plain_cfg, MIX)
        full = fam.init_params(TOY, 11)
        step.set_params({k: full[k] for k in fam.param_shapes(plain_cfg)})
        plain = float(np.asarray(step.run(one_feed)).ravel()[0])
    assert not [op for op in step.main.global_block().ops
                if op.attrs.get("op_namescope")]
    np.testing.assert_allclose(zero, plain, rtol=1e-6)


def test_the_mtp_term_is_over_every_position_but_the_last(one_feed):
    """The program's ``loss_mtp`` against the reference's term, which
    weights the last position 0 and divides by S - 1: (full - main) / 0.3.
    A mean over all S positions with the last one's target wrapped round
    would differ."""
    import compare

    fam = _family()
    with jax.default_matmul_precision("highest"):
        step = fam.build(TOY, MIX)
        step.set_params(fam.init_params(TOY, 11))
        var = [v for v in step.main.global_block().vars
               if v.startswith("loss_mtp")]
        assert len(var) == 1
        with step.fluid.scope_guard(step.scope):
            got, = step.exe.run(step.main, feed=one_feed, fetch_list=var)
        params = fam.init_params(TOY, 11)
        full = float(fam.reference_loss(TOY, compare.matmul("f32"))(
            params, one_feed))
        main = float(fam.reference_loss(TOY, compare.matmul("f32"),
                                        fault="no_mtp")(params, one_feed))
    want = (full - main) / 0.3
    np.testing.assert_allclose(float(np.asarray(got).ravel()[0]), want,
                               rtol=2e-5)
    # every position near log(96) at init: a 40th of a term apart
    assert abs(want * 39 / 40 - want) > 1e-3


def test_the_mtp_gradient_reaches_the_embedding_the_head_and_layer_0(
        one_feed):
    """The first gradient with the MTP term in the loss and with its
    weight 0, same weights and batch: the shared embedding and head and
    the first layer's leaves all move (no stop-gradient on ``h``)."""
    with jax.default_matmul_precision("highest"):
        _, with_mtp, _ = _first_loss_and_moments(TOY, one_feed)
        _, without, _ = _first_loss_and_moments(
            dict(TOY, mtp_loss_factor=0.0), one_feed)
    for k in ("embed_tokens", "lm_head_w", "final_norm", "layer_0_norm1",
              "layer_0_mla_q_a_w", "layer_0_mlp_down_w", "layer_4_moe_up_w"):
        gap = np.linalg.norm(with_mtp[k] - without[k])
        assert gap > 1e-2 * np.linalg.norm(without[k]), k
    assert np.abs(without["mtp_0_eh_proj_w"]).max() == 0


# -- the shares -----------------------------------------------------------------
def test_eight_ranks_shares_and_one_shared_expert_add_up_to_the_layer():
    """64 experts under top-4: 8 ranks of 8 (offsets 0-56), each through
    ``decoder_blocks.routed_experts`` with ITS config (sigmoid, factor
    1.8), plus the shared expert ONCE, against the uncut layer computed
    densely."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.models import decoder_blocks, glm4_moe_lite

    T, h, f, E, k, held = 24, 16, 8, 64, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    x = jax.random.normal(ks[0], (1, T, h))
    router = jax.random.normal(ks[1], (h, E))
    wg, wu = (0.3 * jax.random.normal(ks[i], (E, h, f)) for i in (2, 3))
    wd = 0.3 * jax.random.normal(ks[4], (E, f, h))
    sg, su = (0.3 * jax.random.normal(ks[i], (h, f)) for i in (5, 6))
    sd = 0.3 * jax.random.normal(ks[7], (f, h))
    bias = 0.05 * np.random.default_rng(1).standard_normal(E).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[1, T, h], append_batch_size=False)
        parts = []
        for r in range(E // held):
            cfg = glm4_moe_lite.Glm4MoeLiteConfig.from_dict(dict(
                TOY, hidden_size=h, moe_intermediate_size=f,
                n_routed_experts=held, num_experts_total=E,
                num_experts_per_tok=k, expert_offset=r * held))
            parts.append(decoder_blocks.routed_experts(xv, cfg, "r%d" % r))
        shared = decoder_blocks.mlp(xv, f, cfg, "shared")
    scope = fluid.Scope()
    with fluid.scope_guard(scope), jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        for r in range(E // held):
            sl = slice(r * held, (r + 1) * held)
            for name, val in (("router_w", router), ("router_bias", bias),
                              ("gate_w", wg[sl]), ("up_w", wu[sl]),
                              ("down_w", wd[sl])):
                scope.set_var("r%d_%s" % (r, name), jnp.array(val))
        for name, val in (("gate", sg), ("up", su), ("down", sd)):
            scope.set_var("shared_%s_w" % name, jnp.array(val))
        outs = exe.run(main, feed={"x": np.asarray(x)},
                       fetch_list=parts + [shared])
        x2 = x[0]
        score = jax.nn.sigmoid(x2 @ router)
        _, top = jax.lax.top_k(score + bias, k)
        chosen = jnp.sum(jax.nn.one_hot(top, E), 1)
        w = score * chosen / jnp.sum(score * chosen, -1, keepdims=True) * 1.8
        mlp = lambda g, u, d: (jax.nn.silu(x2 @ g) * (x2 @ u)) @ d  # noqa: E731
        uncut = sum(w[:, e:e + 1] * mlp(wg[e], wu[e], wd[e])
                    for e in range(E)) + mlp(sg, su, sd)
    assert len(outs) == 9
    assert sum(np.abs(o).max() > 0 for o in outs[:8]) >= 6
    np.testing.assert_allclose((sum(outs[:8]) + outs[8])[0], uncut,
                               rtol=1e-4, atol=1e-5)


# -- the module attribution --------------------------------------------------------
def test_the_mtp_modules_instructions_are_filed_under_it(one_feed):
    """Under recomputation: the compiled step's instructions whose
    ``op_name`` carries the ``module.mtp`` scope are the module's
    forward, its replay inside the backward and its backward, and the
    region table files them by (phase, op type) as it files the rest."""
    from paddle_tpu.fluid import profiler

    with jax.default_matmul_precision("highest"):
        _first_loss_and_moments(TOY, one_feed,
                                mix=dict(MIX, recompute=True))
    names = profiler._newest_op_names()
    modules = profiler.newest_step_modules()
    regions = profiler.newest_step_regions()
    assert set(modules) == set(regions) == set(names)
    mtp = [n for n, m in modules.items() if m == "mtp"]
    phases = {regions[n][0] for n in mtp}
    assert {"forward", "backward"} <= phases
    assert any("rematted_computation" in names[n] for n in mtp)
    assert {regions[n][1] for n in mtp} >= {
        "lookup_table", "mul", "fused_multihead_attention", "moe_experts",
        "softmax_with_cross_entropy"}
    assert set(modules.values()) == {"", "mtp"}
    assert profiler.module_of(
        "jit(step)/autodiff/transpose(jvp(module.mtp))/mul/dot_general") \
        == "mtp"
    assert profiler.module_of("jit(step)/layer_3_mul/dot_general") == ""
    assert profiler.region_of(
        "jit(step)/module.mtp/layer_3_mul/dot_general") == ("forward", "mul")


# -- the config and the cell ----------------------------------------------------------
def test_config_takes_published_names_and_startup_draws_as_it_says():
    """The class defaults are the published model's; ``from_dict`` of the
    cell's own file passes over its notes; a keyword the class lacks is
    refused; the startup program draws every matrix at
    ``initializer_range`` and the selection-only biases at zero."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import glm4_moe_lite

    whole = glm4_moe_lite.Glm4MoeLiteConfig()
    assert (whole.num_hidden_layers, whole.n_routed_experts,
            whole.vocab_size, whole.q_lora_rank, whole.v_head_dim) == (
        47, 64, 154880, 768, 256)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        cell = json.load(f)
    cfg = glm4_moe_lite.Glm4MoeLiteConfig.from_dict(cell)
    assert not hasattr(cfg, "assumed") and not hasattr(cfg, "published")
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.num_experts_total,
            cfg.vocab_size, cfg.mtp_loss_factor) == (5, 8, 64, 19360, 0.3)
    assert cfg.num_experts_per_tok == 4 and cfg.routed_scaling_factor == 1.8
    with pytest.raises(TypeError, match="Glm4MoeLiteConfig has no key"):
        glm4_moe_lite.Glm4MoeLiteConfig(use_qk_norm=True)
    with pytest.raises(AssertionError):
        glm4_moe_lite.Glm4MoeLiteConfig(partial_rotary_factor=0.5)
    small = glm4_moe_lite.Glm4MoeLiteConfig.from_dict(
        dict(TOY, vocab_size=2048))
    main, startup, _ = glm4_moe_lite.build_train_program(small, 1, 16,
                                                         use_amp=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor().run(startup)
    assert abs(float(np.std(scope.find_var("embed_tokens"))) - 0.02) < 0.002
    assert abs(float(np.std(scope.find_var("lm_head_w"))) - 0.02) < 0.002
    assert float(np.abs(scope.find_var("mtp_0_moe_router_bias")).max()) == 0


def test_the_cells_parameters_and_work_as_reckoned():
    """At the cell's shapes: 706,518,528 trainable parameters (11.30e9 B
    at 16 B each), and the issue's ~29.7 TFLOP a step, 12.4 of them the
    six layers' causal attention."""
    import run as harness

    fam = _family()
    _, _, cfg, mix, _, _, _ = harness.load_cell(CELL)
    assert sum(int(np.prod(s)) for s in fam.param_shapes(cfg).values()) \
        == 706518528
    assert abs(fam.flops(cfg, mix) / 1e12 - 29.7) < 0.3
    pairs = 8192 * 8193 // 2
    assert fam.attention_cost(cfg, mix) == (
        6 * 20 * pairs * (2 * 512 + 2 * 768 + 2 * 1024),
        6 * 20 * 8192 * 2048 * 2)
    assert fam.tokens_per_step(cfg, mix) == 8192
    assert fam.expected_kernel_tiers(cfg, mix) == ("flash", "flash_bwd")


def test_the_cells_rehearsal_is_correct():
    """``run.py --rehearse-cpu``: the tiny preset (S = 256: the block
    attention tier under the interpreter) through the harness's whole
    path, judged by the rehearsal limits."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483777", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "grad_mid",
                                     "delta_gap", "delta_mid"}
