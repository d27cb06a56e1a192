"""Recompute (activation checkpointing) — reference ``optimizer.py:3341``
``RecomputeOptimizer`` / ``backward.py:576``. The autodiff lowering must
(a) produce identical gradients with and without checkpoints and (b)
actually rematerialize: the compiled HLO re-executes forward matmuls in
the backward pass (jax.checkpoint's optimization barriers keep XLA from
CSE-ing them away)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, optimizer


def _build(use_recompute):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 11
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[32], dtype="float32")
        h1 = layers.fc(x, 64, act="tanh")
        h2 = layers.fc(h1, 64, act="tanh")
        h3 = layers.fc(h2, 64, act="tanh")
        loss = layers.mean(layers.fc(h3, 1))
        opt = optimizer.SGD(learning_rate=0.1)
        if use_recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints([h1, h2])
        opt.minimize(loss)
    return main, startup, loss


def _train(use_recompute, steps=4):
    main, startup, loss = _build(use_recompute)
    exe = fluid.Executor()
    rng = np.random.RandomState(3)
    feed = {"x": rng.rand(8, 32).astype(np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0]).ravel()[0])
                for _ in range(steps)]


def _step_function(main, startup, loss, feed):
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        return exe.as_function(main, feed, [loss])


def _lowered(main, startup, loss, feed):
    """The step's StableHLO text."""
    import jax

    fn, args = _step_function(main, startup, loss, feed)
    return jax.jit(fn).lower(*args).as_text()


def _live_jaxpr(main, startup, loss, feed):
    """The step's jaxpr with what its outputs (the fetched loss, the
    state, the rng key) do not need taken away."""
    import jax
    from jax.interpreters import partial_eval as pe

    fn, args = _step_function(main, startup, loss, feed)
    closed = jax.make_jaxpr(fn)(*args)
    return pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))[0]


def test_recompute_matches_baseline():
    base = _train(False)
    remat = _train(True)
    np.testing.assert_allclose(base, remat, rtol=1e-5)


def test_recompute_actually_rematerializes():
    feed = {"x": np.zeros((8, 32), np.float32)}
    base = _lowered(*_build(False), feed)
    remat = _lowered(*_build(True), feed)
    # jax.checkpoint emits optimization_barrier (so XLA can't CSE the
    # recompute away) and duplicates the checkpointed segments' matmuls
    assert remat.count("optimization_barrier") > 0
    assert remat.count("dot_general") > base.count("dot_general"), (
        "checkpointed program lowered to no extra matmuls: "
        "jax.checkpoint segments were not applied")


# -- what a segment keeps: kernels.common.keep_across_recompute --------------
# Two layers of projections -> (sparse_index ->) fused attention -> output
# projection, a checkpoint after each, on the Pallas interpreter; tile 64,
# so S = 128 is 2 x 2 tiles and takes the select / flash tier.
_B, _S, _H, _HKV, _D, _HI, _DI, _TOPK, _LAYERS = 1, 128, 4, 2, 16, 2, 8, 24, 2
_KEPT_BYTES = {     # a layer: o and lse in float32, the mask a byte an entry
    "attn_select": _B * _H * _S * (_D + 1) * 4,
    "attn_flash": _B * _H * _S * (_D + 1) * 4,
    "sparse_index": _B * _S * _S}
_TIERS = {"select": ("attn_select", "sparse_index"), "flash": ("attn_flash",)}


@pytest.fixture
def interpreted_tiers(monkeypatch):
    from paddle_tpu.kernels import attention as A

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(A, "_SELECT_BLOCK_CANDIDATES", (64,))
    monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
    monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)


def _bare_checkpoint(monkeypatch):
    """The replay as it was before it kept anything: no policy."""
    import jax

    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)


def _attention_program(tier, use_recompute):
    def heads(x, n, d):
        return layers.transpose(layers.reshape(x, [0, 0, n, d]),
                                [0, 2, 1, 3])

    def proj(x, width):
        return layers.fc(x, width, num_flatten_dims=2, bias_attr=False)

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    # (fresh names: two builds of one program lower to one text)
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        h = layers.data("x", shape=[_S, 32], dtype="float32")
        cuts = []
        for _ in range(_LAYERS):
            select = None
            if tier == "select":
                select = layers.sparse_index(
                    heads(proj(h, _HI * _DI), _HI, _DI), proj(h, _DI),
                    proj(h, _HI), _TOPK, chunk_size=64)
            o = layers.fused_attention(
                heads(proj(h, _H * _D), _H, _D),
                heads(proj(h, _HKV * _D), _HKV, _D),
                heads(proj(h, _HKV * _D), _HKV, _D), scale=_D ** -0.5,
                causal=True, num_kv_heads=_HKV, select=select)
            h = h + proj(layers.reshape(layers.transpose(o, [0, 2, 1, 3]),
                                        [0, 0, _H * _D]), 32)
            cuts.append(h)
        loss = layers.mean(proj(h, 1))
        opt = optimizer.SGD(learning_rate=0.1)
        if use_recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(cuts)
        opt.minimize(loss)
    feed = {"x": np.random.RandomState(0).randn(_B, _S, 32).astype(
        np.float32)}
    return main, startup, loss, feed


def _kept_bytes(what):
    from paddle_tpu.fluid import monitor

    return monitor.counter("recompute_kept_bytes_total",
                           labels={"what": what}).value


def _equations(jaxpr, outer=""):
    """``(equation, its name stack from the step down)`` through every
    nested jaxpr; ``transpose(`` in a stack = made in the backward pass."""
    import jax

    for eqn in jaxpr.eqns:
        stack = outer + str(eqn.source_info.name_stack)
        yield eqn, stack
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, stack + "/")


def _bisections(jaxpr, transposed):
    """``kth_largest``'s 32-pass loops in a jaxpr, by whether they sit
    under a transpose (= are made again in the backward pass)."""
    return sum(("transpose(" in stack) == transposed
               for eqn, stack in _equations(jaxpr)
               if eqn.primitive.name == "scan"
               and eqn.params["length"] == 32)


def _three_steps(program):
    """Three steps' losses and every gradient of every step."""
    main, startup, loss, feed = program
    ad = next(op for op in main.global_block().ops if op.type == "autodiff")
    fetch = [loss] + list(ad.attr("grad_names"))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        return [np.asarray(x) for _ in range(3)
                for x in exe.run(main, feed=feed, fetch_list=fetch)]


def _assert_as_under_the_bare_checkpoint(monkeypatch, program, leaves):
    """``program()``'s three steps, bit for bit what the bare checkpoint
    gives: the backward pass reads from a buffer the bits a second making
    would have produced."""
    got = _three_steps(program())
    with monkeypatch.context() as m:
        _bare_checkpoint(m)
        want = _three_steps(program())
    assert len(got) == len(want) >= 3 * leaves
    assert got[0] != got[-len(got) // 3]       # the steps train
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "bare"])
@pytest.mark.parametrize("tier", ["select", "flash"])
def test_a_kept_value_is_made_once_a_layer(interpreted_tiers, monkeypatch,
                                           tier, kept):
    """Under checkpoints the forward attention kernel (and the selection's
    bisection) is in the step once a layer: its ``o``, row logsumexp and
    mask cross the boundary. Under the bare checkpoint each is there twice,
    the second time in the backward pass."""
    import collections

    from test_autodiff_one_forward import _count

    if not kept:
        _bare_checkpoint(monkeypatch)
    live = _live_jaxpr(*_attention_program(tier, True))
    counts = collections.Counter()
    _count(live, counts)
    fwd = "attn_%s_fwd" % tier
    replayed = 0 if kept else _LAYERS
    assert counts[(fwd, False)] == _LAYERS
    assert counts[(fwd, True)] == replayed
    assert counts[("attn_%s_bwd_dq" % tier, True)] == _LAYERS
    assert counts[("attn_%s_bwd_dkv" % tier, True)] == _LAYERS
    assert sum(n for (name, _), n in counts.items()
               if name.startswith("attn_")) == 3 * _LAYERS + replayed
    if tier == "select":
        # as many as the program without checkpoints holds: one selection
        # a layer (a selection walks its chunks in groups, a loop each)
        once = _bisections(_live_jaxpr(*_attention_program(tier, False)),
                           False)
        assert once > 0 and once % _LAYERS == 0
        assert _bisections(live, False) == once
        assert _bisections(live, True) == (0 if kept else once)
    # the projections inside a segment are still made again
    assert counts[("dot_general", True)] > 0


@pytest.mark.parametrize("tier", ["select", "flash"])
def test_kept_values_change_no_number(interpreted_tiers, monkeypatch, tier):
    _assert_as_under_the_bare_checkpoint(
        monkeypatch, lambda: _attention_program(tier, True), 10)


@pytest.mark.parametrize("tier", ["select", "flash"])
def test_kept_bytes_counter_reads_the_shapes(interpreted_tiers, tier):
    """``recompute_kept_bytes_total{what}``: once a site traced inside a
    checkpointed segment; a program without checkpoints counts nothing."""
    before = {w: _kept_bytes(w) for w in _KEPT_BYTES}
    _live_jaxpr(*_attention_program(tier, False))
    assert {w: _kept_bytes(w) for w in _KEPT_BYTES} == before
    _live_jaxpr(*_attention_program(tier, True))
    for what, a_layer in _KEPT_BYTES.items():
        want = _LAYERS * a_layer if what in _TIERS[tier] else 0
        assert _kept_bytes(what) - before[what] == want, what


@pytest.mark.parametrize("tier", ["select", "flash"])
def test_without_checkpoints_the_tags_lower_to_nothing(interpreted_tiers,
                                                       monkeypatch, tier):
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.kernels import common

    tagged = _lowered(*_attention_program(tier, False))
    monkeypatch.setattr(A, "keep_across_recompute", lambda x, what: x)
    monkeypatch.setattr(common, "keep_across_recompute", lambda x, what: x)
    assert _lowered(*_attention_program(tier, False)) == tagged


def test_an_untagged_segment_lowers_as_under_the_bare_checkpoint(
        monkeypatch):
    def lowered():
        with fluid.unique_name.guard():
            return _lowered(*_build(True),
                            {"x": np.zeros((8, 32), np.float32)})

    kept = lowered()
    _bare_checkpoint(monkeypatch)
    assert lowered() == kept


# -- the expert layer's dispatch plan: made once a layer-step ----------------
# Two layers of router -> held experts (4 of 8 under top-2, a walk of 2
# chunks of 32 rows) added to the stream, a checkpoint after each.
_MOE_T, _MOE_K, _MOE_HELD = 32, 2, 4
_MOE_BLOCKS = _MOE_HELD     # 32 tokens: one block of 128 an expert
# a layer: each sorted row's place, starts, ends, n_here (int32), and the
# held experts' weight table (f32)
_MOE_KEPT_BYTES = 4 * (_MOE_T * _MOE_K + 2 * _MOE_HELD + 1
                       + _MOE_BLOCKS * 128)


def _moe_program(use_recompute):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        h = layers.data("x", shape=[_MOE_T, 16], append_batch_size=False)
        cuts = []
        for _ in range(_LAYERS):
            ids, wts = layers.moe_route(h, 8, _MOE_K)
            h = h + layers.moe_experts(h, ids, wts, _MOE_HELD, 8,
                                       expert_offset=2)
            cuts.append(h)
        loss = layers.mean(layers.fc(h, 1, bias_attr=False))
        opt = optimizer.SGD(learning_rate=0.1)
        if use_recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints(cuts)
        opt.minimize(loss)
    feed = {"x": np.random.RandomState(0).randn(_MOE_T, 16).astype(
        np.float32)}
    return main, startup, loss, feed


def _plan_equations(jaxpr):
    """``[forward, transposed]``: equations under the plan's scope
    (``moe_plan``: the held table's counts and a chunk's rows), by whether
    they sit under a transpose (= are made again in the backward pass)."""
    n = [0, 0]
    for _, stack in _equations(jaxpr):
        if "moe_plan" in stack:
            n["transpose(" in stack] += 1
    return n


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "bare"])
def test_the_dispatch_plan_is_made_once_a_layer(monkeypatch, kept):
    """Under checkpoints the step holds the expert layer's plan once a
    layer, as the program without checkpoints does: the backward walk goes
    by the rows the forward walk wrote. Under the bare checkpoint the
    recomputed segment makes it again."""
    import collections

    from test_autodiff_one_forward import _count

    if not kept:
        _bare_checkpoint(monkeypatch)
    once = _plan_equations(_live_jaxpr(*_moe_program(False)))
    assert once[0] > 0 and once[0] % _LAYERS == 0 and once[1] == 0
    live = _live_jaxpr(*_moe_program(True))
    fwd, again = _plan_equations(live)
    assert fwd == once[0]
    assert again == (0 if kept else once[0])
    # nor is the router's top_k made again: its values and ids are kept
    counts = collections.Counter()
    _count(live, counts)
    assert counts[("top_k", False)] == _LAYERS
    assert counts[("top_k", True)] == (0 if kept else _LAYERS)
    # the router's matmul and the experts' grouped GEMMs still are
    assert counts[("dot_general", True)] > 0
    assert counts[("ragged_dot_general", True)] > 0


def test_a_kept_plan_changes_no_number(monkeypatch):
    _assert_as_under_the_bare_checkpoint(
        monkeypatch, lambda: _moe_program(True), 9)


def test_kept_plan_bytes_are_counted_once_a_site():
    before = _kept_bytes("moe_plan")
    _live_jaxpr(*_moe_program(False))
    assert _kept_bytes("moe_plan") == before
    route = _kept_bytes("moe_route")
    _live_jaxpr(*_moe_program(True))
    assert _kept_bytes("moe_plan") - before == _LAYERS * _MOE_KEPT_BYTES
    # the chosen experts' scores (f32) and ids (int32)
    assert _kept_bytes("moe_route") - route == _LAYERS * 2 * 4 * _MOE_T * _MOE_K


def test_without_checkpoints_the_plans_mark_lowers_to_nothing(monkeypatch):
    from paddle_tpu.kernels import common

    tagged = _lowered(*_moe_program(False))
    monkeypatch.setattr(common, "keep_across_recompute", lambda x, what: x)
    assert _lowered(*_moe_program(False)) == tagged


# -- the delta rule's residuals: the forward kernel once a layer-step --------
# One layer of projections -> gated delta rule (head dims of 128, so the
# Pallas kernels on the interpreter) -> output projection, a checkpoint
# after it; the scalar rule (two value heads on one key head) and the
# channel-gated rule (a decay a key channel, two heads).
_DR_S, _DR_D, _DR_HV = 128, 128, 2
_DR_HK = {"scalar": 1, "channel": 2}
_DR_FWD = {"scalar": "gdn_chunk_fwd", "channel": "kda_chunk_fwd"}
# a layer, a value head each: o in the program's float32, the state
# entering each group of 128 positions and the group's inverses in float32
_DR_KEPT_BYTES = 4 * _DR_HV * (_DR_S * _DR_D
                               + _DR_S // 128 * (_DR_D * _DR_D + 128 * 128))


def _delta_rule_program(rule, use_recompute):
    def proj(x, width):
        return layers.fc(x, width, num_flatten_dims=2, bias_attr=False)

    def heads(x, n):
        return layers.reshape(proj(x, n * _DR_D), [0, 0, n, _DR_D])

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 5
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        h = layers.data("x", shape=[_DR_S, 32], dtype="float32")
        hk = _DR_HK[rule]
        gate = heads(h, _DR_HV) if rule == "channel" else proj(h, _DR_HV)
        o = layers.gated_delta_rule(heads(h, hk), heads(h, hk),
                                    heads(h, _DR_HV), gate, proj(h, _DR_HV))
        h = h + proj(layers.reshape(o, [0, 0, _DR_HV * _DR_D]), 32)
        loss = layers.mean(proj(h, 1))
        opt = optimizer.SGD(learning_rate=0.1)
        if use_recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints([h])
        opt.minimize(loss)
    feed = {"x": np.random.RandomState(0).randn(1, _DR_S, 32).astype(
        np.float32)}
    return main, startup, loss, feed


@pytest.fixture
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "bare"])
@pytest.mark.parametrize("rule", ["scalar", "channel"])
def test_the_delta_rule_kernel_runs_once_a_layer(interpreted_kernels,
                                                 monkeypatch, rule, kept):
    """Under a checkpoint the compiled step holds the forward kernel at one
    site (the primal pass): its ``o``, entering states and inverses cross
    the boundary, and the backward kernel reads them. Under the bare
    checkpoint the replay runs the forward kernel again."""
    import collections

    import jax
    from test_autodiff_one_forward import _count

    from paddle_tpu.fluid import profiler

    if not kept:
        _bare_checkpoint(monkeypatch)
    before = _kept_bytes("delta_rule")
    program = _delta_rule_program(rule, True)
    live = _live_jaxpr(*program)
    assert _kept_bytes("delta_rule") - before == _DR_KEPT_BYTES
    counts = collections.Counter()
    _count(live, counts)
    fwd = _DR_FWD[rule]
    bwd = fwd.replace("_fwd", "_bwd")
    assert counts[(fwd, False)] == 1
    assert counts[(fwd, True)] == (0 if kept else 1)
    assert counts[(bwd, True)] == 1
    # the compiled step: a call site = the scope its instructions sit under
    fn, args = _step_function(*program)
    names = profiler.op_names_of(jax.jit(fn).lower(*args).compile()
                                 .as_text())
    mark = "/%s/" % fwd
    sites = {n.split(mark)[0] for n in names.values() if mark in n}
    assert len(sites) == (1 if kept else 2), sorted(sites)


@pytest.mark.parametrize("rule", ["scalar", "channel"])
def test_kept_delta_rule_residuals_change_no_number(interpreted_kernels,
                                                    monkeypatch, rule):
    _assert_as_under_the_bare_checkpoint(
        monkeypatch, lambda: _delta_rule_program(rule, True), 8)


@pytest.mark.parametrize("rule", ["scalar", "channel"])
def test_without_checkpoints_the_delta_rule_mark_lowers_to_nothing(
        interpreted_kernels, monkeypatch, rule):
    from paddle_tpu.kernels import delta_rule

    before = _kept_bytes("delta_rule")
    tagged = _lowered(*_delta_rule_program(rule, False))
    assert _kept_bytes("delta_rule") == before
    monkeypatch.setattr(delta_rule, "keep_across_recompute",
                        lambda x, what: x)
    assert _lowered(*_delta_rule_program(rule, False)) == tagged
