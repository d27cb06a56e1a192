"""The compiled training step holds ONE forward (``ops/autodiff.py``): the
``autodiff`` op rebinds the forward names to its replay's values, so the
primal lowering is dead code. Counted where CSE cannot help - a step with
Pallas calls in its forward - and checked to change no number."""

import collections

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer
from paddle_tpu.fluid.contrib import mixed_precision
from paddle_tpu.fluid.registry import registry
from paddle_tpu.models import bert
from test_recompute import _build as _build_recompute
from test_recompute import _live_jaxpr
from test_sparse import _build_emb_sgd

SEQ = 64


def _bert_train_program(mode):
    """``BertConfig.tiny()`` pretraining on the fused (Pallas) attention, as
    ``bert.build_pretrain_program`` builds it but for the optimizer's
    wrapper; ``recompute`` checkpoints layer 0's output: two segments, the
    first rematerialized in the backward."""
    cfg = bert.BertConfig.tiny()
    cfg.hidden_dropout = cfg.attn_dropout = 0.0   # no interpreter PRNG
    cfg.use_fused_attention = True
    n_pred = bert.max_predictions(SEQ)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[SEQ], dtype="int64")
        pos = layers.data("pos_ids", shape=[SEQ], dtype="int64")
        sent = layers.data("sent_ids", shape=[SEQ], dtype="int64")
        imask = layers.data("input_mask", shape=[SEQ, 1], dtype="float32")
        enc = bert.bert_encoder(src, pos, sent, imask, cfg)
        mpos = layers.data("mask_pos", shape=[n_pred], dtype="int64")
        mlabel = layers.data("mask_label", shape=[n_pred], dtype="int64")
        mweight = layers.data("mask_weight", shape=[n_pred],
                              dtype="float32")
        loss = bert.mlm_loss_masked(enc, mpos, mlabel, mweight, cfg)
        opt = optimizer.Adam(1e-4)
        if mode == "amp":
            opt = mixed_precision.decorate(opt)
        elif mode == "recompute":
            norms = [op.output("Y")[0] for op in main.global_block().ops
                     if op.type == "layer_norm"]
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints([norms[2]])   # the embeddings', 2 a layer
        opt.minimize(loss)
    return cfg, main, startup, loss


def _count(jaxpr, counts, outer=""):
    """Equations by (primitive or kernel name, under a transpose), through
    every nested jaxpr but a kernel's own body."""
    for eqn in jaxpr.eqns:
        stack = outer + str(eqn.source_info.name_stack)
        name = eqn.primitive.name
        if name == "pallas_call":
            counts[(eqn.params["name"], "transpose(" in stack)] += 1
            continue
        counts[(name, "transpose(" in stack)] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, counts, stack + "/")


def _live_counts(main, startup, loss, feed):
    """Counts over the step's live jaxpr."""
    counts = collections.Counter()
    _count(_live_jaxpr(main, startup, loss, feed), counts)
    return counts


@pytest.mark.parametrize("mode", ["plain", "amp", "recompute"])
def test_the_step_holds_one_forward(mode, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cfg, main, startup, loss = _bert_train_program(mode)
    counts = _live_counts(main, startup, loss,
                          bert.synthetic_batch(cfg, 2, SEQ))
    # q, k, v, out, ffn1, ffn2 a layer, the MLM transform and the decoder
    one_forward = 6 * cfg.n_layers + 2
    remat_layers = 1 if mode == "recompute" else 0
    assert counts[("dot_general", False)] == one_forward
    assert counts[("attn_block_fwd", False)] == cfg.n_layers
    assert counts[("attn_block_bwd", True)] == cfg.n_layers
    # only what a checkpoint segment rematerializes runs forward again
    assert counts[("attn_block_fwd", True)] == remat_layers
    assert sum(n for (name, _), n in counts.items()
               if name.startswith("attn_")) == 2 * cfg.n_layers + remat_layers


# -- rebinding changes no number ---------------------------------------------
def _primal_bound(monkeypatch):
    """Bypass the rebinding: after the ``autodiff`` op every name bound
    before it reads its primal value again (the gradients are new names).
    As the lowering was before it rebound anything."""
    rule = registry.get("autodiff")
    lower = rule.lower

    def lower_then_restore(ctx, op):
        before = dict(ctx.env)
        lower(ctx, op)
        ctx.env.update(before)

    monkeypatch.setattr(rule, "lower", lower_then_restore)


def _metric_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[16], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, 32, act="relu")
        h = layers.batch_norm(h, moving_mean_name="bn_mean",
                              moving_variance_name="bn_variance")
        h = layers.dropout(h, 0.3)
        logits = layers.fc(h, 4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        optimizer.SGD(0.1).minimize(loss)
        acc = layers.accuracy(layers.softmax(logits), y)   # after minimize
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 16).astype(np.float32),
            "y": rng.randint(0, 4, (8, 1)).astype(np.int64)}
    return main, startup, [loss, h, acc], ["bn_mean", "bn_variance"], feed


def _sparse_program():
    main, startup, loss = _build_emb_sgd(True)
    emb = main.global_block().ops[0].output_arg_names()[0]   # the lookup's
    feed = {"ids": np.array([[1, 2, 2], [7, 1, 1]], np.int64)}
    return main, startup, [loss, emb, "emb_w@GRAD@ROWS"], ["emb_w"], feed


def _recompute_program():
    main, startup, loss = _build_recompute(True)
    block = main.global_block()
    fcs = [op.output("Out")[0] for op in block.ops if op.type == "tanh"]
    feed = {"x": np.random.RandomState(3).rand(8, 32).astype(np.float32)}
    # a checkpoint (handed on by its segment), a value inside the first
    # segment (keeps its primal binding) and one in the last segment
    pre = next(op for op in block.ops if op.type == "mul").output("Out")[0]
    return main, startup, [loss, fcs[0], pre, fcs[2]], [], feed


def _two_steps(build):
    """Two steps' fetches (the program's own, then every gradient) and the
    state the second step committed."""
    main, startup, fetch, state, feed = build()
    ad = next(op for op in main.global_block().ops if op.type == "autodiff")
    fetch = list(fetch) + list(ad.attr("grad_names"))
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = [exe.run(main, feed=feed, fetch_list=fetch) for _ in range(2)]
        out.append([np.asarray(scope.find_var(n)) for n in state])
    return [np.asarray(x) for step in out for x in step]


@pytest.mark.parametrize("compiled", [False, True])
@pytest.mark.parametrize("build", [_metric_program, _sparse_program,
                                   _recompute_program])
def test_rebinding_changes_no_number(build, compiled, monkeypatch):
    """Op by op the replay is the primal bit for bit. Compiled, XLA fuses
    (and contracts) a forward that the backward reads differently from one
    that it does not: a few units in the last place, nothing more."""
    with jax.disable_jit(not compiled):
        rebound = _two_steps(build)
        _primal_bound(monkeypatch)
        primal = _two_steps(build)
    assert len(rebound) == len(primal) > 6
    for got, want in zip(rebound, primal):
        assert got.dtype == want.dtype and got.shape == want.shape
        if compiled:
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(got, want)


def test_the_bypass_keeps_the_primal_forward(monkeypatch):
    """The control of the test above: with the rebinding bypassed the step
    holds the second forward again."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    _primal_bound(monkeypatch)
    cfg, main, startup, loss = _bert_train_program("plain")
    counts = _live_counts(main, startup, loss,
                          bert.synthetic_batch(cfg, 2, SEQ))
    assert counts[("attn_block_fwd", False)] == 2 * cfg.n_layers
    assert counts[("dot_general", False)] == 2 * (6 * cfg.n_layers + 2)
