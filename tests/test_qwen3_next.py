"""Qwen3-Next's block through ``fluid.layers`` and ``Executor.run`` against
the plain float32 reference (``benchmark/families/qwen3_next_train.py``,
which imports nothing of the program): the model's loss and every leaf's
gradient over three Adam steps, the chunked gated delta rule against the
step-by-step recurrence, the dropless expert op under a skewed routing,
and the shares test - the parts that 16 expert-parallel ranks compute add
up to the uncut layer."""

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

TOY = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=10000000,
    rms_norm_eps=1e-6, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=4, num_experts_total=16, expert_offset=4,
    num_experts_per_tok=3, norm_topk_prob=True, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, initializer_range=0.02,
    gdn_chunk_size=16, learning_rate=1e-3, amp="off")
MIX = dict(batch=2, seq_len=40, recompute=False)    # 40: not a chunk multiple


def _family():
    import run as harness

    return harness.load_module("families", "qwen3_next_train")


@pytest.fixture(scope="module")
def both_sides():
    """Three Adam steps of the program (through ``Executor.run``) and of
    the reference, from one seed: losses, the first gradient of every
    leaf (the program's read back from Adam's first moment), and every
    leaf after the three steps."""
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
        from paddle_tpu.fluid import profiler

        got["regions"] = profiler.newest_step_regions()

        loss_fn = fam.reference_loss(TOY, compare.matmul("f32"))
        opt = fam.optimizer(TOY)
        p = dict(params)
        m = {k: jnp.zeros_like(v) for k, v in p.items()}
        v2 = {k: jnp.zeros_like(v) for k, v in p.items()}
        ref = {"loss": []}
        for i, feed in enumerate(feeds):
            loss, g = jax.value_and_grad(loss_fn)(p, feed)
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
    return got, ref


def test_losses_match_reference_over_three_steps(both_sides):
    got, ref = both_sides
    np.testing.assert_allclose(got["loss"], ref["loss"], rtol=2e-5)


_KINDS = sorted({k.split("_", 2)[2] if k.startswith("layer_") else k
                 for k in _family().param_shapes(TOY)})


@pytest.mark.parametrize("kind", _KINDS)
def test_every_leafs_gradient_and_update_match_reference(both_sides, kind):
    """Each leaf of this kind, in every layer that has one: the first
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
        # Adam's first steps move every element by ~lr whatever the
        # gradient's size, so a near-zero gradient's sign decides: hold
        # the elements whose gradient is not noise
        clear = np.abs(ref["grad"][k]) > 1e-3 * scale
        np.testing.assert_allclose(
            got["params"][k][clear], ref["params"][k][clear],
            rtol=1e-3, atol=2e-4, err_msg=k)


def _recurrence(q, k, v, g, beta):
    """The gated delta rule, one position at a time."""
    B, S, H, dk = q.shape

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        decayed = state * jnp.exp(g_t)[..., None, None]
        delta = b_t[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", decayed, k_t))
        state = decayed + k_t[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _delta_rule_inputs(S):
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, H, dk, dv = 2, 3, 16, 24
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / 4.0
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -0.5 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [64, 32, 16])
def test_chunked_delta_rule_matches_recurrence(chunk):
    """S = 150 is a multiple of no chunk size tried; 64 and 32 go through
    the block inversion's merge, 16 through forward substitution alone."""
    from paddle_tpu.fluid.ops.linear_attention import (
        gated_delta_rule_chunked)

    args = _delta_rule_inputs(150)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule_chunked(*args, chunk_size=chunk)
        want = _recurrence(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)


def test_chunked_delta_rule_gradients_match_recurrence():
    from paddle_tpu.fluid.ops.linear_attention import (
        gated_delta_rule_chunked)

    args = _delta_rule_inputs(150)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(
            gated_delta_rule_chunked(*a, chunk_size=64))),
            argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(_recurrence(*a))),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for a, b, name in zip(got, want, "q k v g beta".split()):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()),
                                   err_msg=name)


def _expert(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _moe_inputs(T=48, h=8, f=12, E_total=32, k=3, skew=None):
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    x = jax.random.normal(ks[0], (T, h))
    wg = 0.3 * jax.random.normal(ks[1], (E_total, h, f))
    wu = 0.3 * jax.random.normal(ks[2], (E_total, h, f))
    wd = 0.3 * jax.random.normal(ks[3], (E_total, f, h))
    logits = jax.random.normal(ks[4], (T, E_total))
    if skew is not None:        # one expert takes a pair of every token
        logits = logits.at[:, skew].add(8.0)
    vals, ids = jax.lax.top_k(jax.nn.softmax(logits), k)
    return x, ids, vals / vals.sum(-1, keepdims=True), wg, wu, wd


def _dense(x, ids, wts, wg, wu, wd, lo, hi):
    """Experts lo..hi applied to every token under a 0/1 mask."""
    out = 0.0
    for e in range(lo, hi):
        col = jnp.sum(jnp.where(ids == e, wts, 0.0), -1)
        out = out + col[:, None] * _expert(x, wg[e], wu[e], wd[e])
    return out


@pytest.mark.parametrize("chunk_rows", [7, 48, 1000])
def test_moe_experts_dropless_under_skewed_routing(chunk_rows):
    """Expert 5 takes a pair of every token (48 of the 144 pairs, on one
    of four held experts): nothing is dropped, whatever the chunking - 7
    rows a chunk walks many chunks and cuts through groups, 1000 holds
    every pair in one."""
    from paddle_tpu.fluid.ops.moe_ops import moe_experts_dropless

    x, ids, wts, wg, wu, wd = _moe_inputs(skew=5)
    assert int(jnp.sum(ids == 5)) == x.shape[0]
    with jax.default_matmul_precision("highest"):
        got = moe_experts_dropless(x, ids, wts, wg[4:8], wu[4:8], wd[4:8],
                                   4, chunk_rows)
        want = _dense(x, ids, wts, wg, wu, wd, 4, 8)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        args = (x, wts, wg[4:8], wu[4:8], wd[4:8])
        g_got = jax.grad(lambda x_, w_, a, b, c: jnp.sum(jnp.sin(
            moe_experts_dropless(x_, ids, w_, a, b, c, 4, chunk_rows))),
            argnums=(0, 1, 2, 3, 4))(*args)
        g_want = jax.grad(lambda x_, w_, a, b, c: jnp.sum(jnp.sin(_dense(
            x_, ids, w_, jnp.zeros_like(wg).at[4:8].set(a),
            jnp.zeros_like(wu).at[4:8].set(b),
            jnp.zeros_like(wd).at[4:8].set(c), 4, 8))),
            argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()))


def _argsort_plan(ids, wts, E, expert_offset, chunk_rows):
    """The dispatch plan as the program made it before PR 37 (a stable
    argsort of the P = T * k pairs by expert, a ``bincount``, a P-long
    gather of the weights): the oracle of the form that follows the pairs
    held. ``(tok, w_sorted, starts, ends, n_here)``."""
    T, k = ids.shape
    P, CH = T * k, int(chunk_rows)
    n_chunks = -(-P // CH)
    local = ids.reshape(P) - expert_offset
    key = jnp.where((local >= 0) & (local < E), local, E)   # E: not held
    order = jnp.argsort(key, stable=True)
    ends = jnp.cumsum(jnp.bincount(key, length=E + 1)[:E])
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    tail = n_chunks * CH - P
    tok = jnp.pad(order // k, (0, tail)).reshape(n_chunks, CH)
    w_sorted = jnp.pad(wts.reshape(P)[order], (0, tail)).reshape(
        n_chunks, CH)
    return tok, w_sorted, starts, ends, ends[-1]


# routing -> (skew, first held expert, held experts) of 32 under top-3
_ROUTINGS = {
    "uniform": (None, 0, 4),
    "one_expert_takes_a_pair_of_every_token": (5, 4, 4),
    "no_pair_held": (None, 40, 4),
    "every_pair_held": (None, 0, 32),
    "expert_offset": (None, 26, 6),
}


@pytest.mark.parametrize("chunk_rows", [7, 48, 1000])
@pytest.mark.parametrize("routing", sorted(_ROUTINGS))
def test_dispatch_plan_equals_the_argsort_forms(routing, chunk_rows):
    """The plan made from the held tables, every chunk of it, against the
    stable argsort's, field by field: the spans, the count, and each
    sorted row's token and weight below ``n_here`` (rows past it are
    selected out of the walk and may hold anything)."""
    from paddle_tpu.fluid.ops import moe_ops

    skew, lo, E = _ROUTINGS[routing]
    _, ids, wts, _, _, _ = _moe_inputs(T=150, skew=skew)
    ids = ids.astype(jnp.int32)
    tok, w_sorted, starts, ends, n_here = _argsort_plan(ids, wts, E, lo,
                                                        chunk_rows)
    held, wtab, count, through, got_starts, got_ends = \
        moe_ops._held_tables(ids, wts, E, lo)
    place = jax.vmap(lambda c: moe_ops._chunk_plan(
        (held, count, through), c, chunk_rows))(
            jnp.arange(tok.shape[0], dtype=jnp.int32))
    n = int(n_here)
    assert n == {"no_pair_held": 0, "every_pair_held": ids.size}.get(
        routing, n) and int(got_ends[-1]) == n
    if routing == "one_expert_takes_a_pair_of_every_token":
        assert int(ends[1] - starts[1]) == ids.shape[0]
    np.testing.assert_array_equal(got_starts, starts)
    np.testing.assert_array_equal(got_ends, ends)
    route = (place, got_starts, got_ends, got_ends[-1])
    got_tok = jax.vmap(lambda c: moe_ops._row_tokens(
        route, c, jnp.zeros((ids.shape[0], 1)), wtab))(
            jnp.arange(tok.shape[0]))
    np.testing.assert_array_equal(np.asarray(got_tok).ravel()[:n],
                                  np.asarray(tok).ravel()[:n])
    np.testing.assert_array_equal(
        np.asarray(wtab).ravel()[np.asarray(place).ravel()[:n]],
        np.asarray(w_sorted).ravel()[:n])
    assert np.all(np.diff(np.asarray(place).ravel()[:n]) > 0)


@pytest.mark.parametrize("n_here, live", [(0, 1), (8, 1), (9, 2), (24, 3),
                                          (25, 4), (40, 4)])
def test_moe_walk_stops_after_the_last_chunk_with_a_held_pair(n_here, live):
    """The walk's trip count follows the load, a chunk at a time: the
    first chunk counts always, and no more chunks than there are."""
    from paddle_tpu.fluid.ops.moe_ops import _live_chunks

    route = (jnp.zeros((4, 8), jnp.int32), None, None, jnp.int32(n_here))
    assert int(_live_chunks(route)) == live


def test_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """The shares test. One expert layer, 32 experts, top-3, as 16 ranks
    hold it (2 experts each): through ``fluid.layers`` and one
    ``Executor.run``, the 16 ranks' routed parts plus the shared expert -
    which every rank computes alike - counted once equal the plain
    uncut layer over all 32 experts."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    x, ids, wts, wg, wu, wd = _moe_inputs()
    T, h = x.shape
    f, E_total, k, held = wg.shape[2], wg.shape[0], ids.shape[1], 2
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    router = jax.random.normal(ks[0], (h, E_total))
    sg, su = (0.3 * jax.random.normal(ks[i], (h, f)) for i in (1, 2))
    sd = 0.3 * jax.random.normal(ks[3], (f, h))
    sr = jax.random.normal(ks[4], (h, 1))

    main, startup = fluid.Program(), fluid.Program()
    named = lambda n: fluid.ParamAttr(name=n)       # noqa: E731
    with fluid.program_guard(main, startup):
        xv = layers.data("x", shape=[T, h], append_batch_size=False)
        pid, pw = layers.moe_route(xv, E_total, k, param_attr=named("router"))
        parts = [layers.moe_experts(
            xv, pid, pw, held, f, expert_offset=r * held,
            gate_attr=named("g%d" % r), up_attr=named("u%d" % r),
            down_attr=named("d%d" % r)) for r in range(E_total // held)]
        fc = lambda v, n, name: layers.fc(          # noqa: E731
            v, n, bias_attr=False, param_attr=named(name))
        shared = layers.elementwise_mul(
            fc(layers.swiglu(fc(xv, f, "sg"), fc(xv, f, "su")), h, "sd"),
            layers.sigmoid(fc(xv, 1, "sr")))
    scope = fluid.Scope()
    with fluid.scope_guard(scope), \
            jax.default_matmul_precision("highest"):
        exe = fluid.Executor()
        exe.run(startup)
        # copies: the run donates what the scope holds
        for name, val in [("router", router), ("sg", sg), ("su", su),
                          ("sd", sd), ("sr", sr)]:
            scope.set_var(name, jnp.array(val))
        for r in range(E_total // held):
            sl = slice(r * held, (r + 1) * held)
            scope.set_var("g%d" % r, wg[sl])
            scope.set_var("u%d" % r, wu[sl])
            scope.set_var("d%d" % r, wd[sl])
        outs = exe.run(main, feed={"x": np.asarray(x)},
                       fetch_list=parts + [shared])
        p = jax.nn.softmax(x @ router)
        vals, top = jax.lax.top_k(p, k)
        uncut = _dense(x, top, vals / vals.sum(-1, keepdims=True),
                       wg, wu, wd, 0, E_total) \
            + jax.nn.sigmoid(x @ sr) * _expert(x, sg, su, sd)
    assert len(outs) == 17 and all(np.abs(o).max() > 0 for o in outs[:16])
    np.testing.assert_allclose(sum(outs[:16]) + outs[16], uncut,
                               rtol=1e-4, atol=1e-5)


def test_new_counters_say_which_implementation_was_traced(both_sides):
    from paddle_tpu.fluid import monitor

    assert monitor.counter("gdn_dispatch_total",
                           labels={"impl": "chunked"}).value > 0
    assert monitor.counter("moe_dispatch_total",
                           labels={"impl": "ragged_loop_held"}).value > 0
    assert monitor.counter("conv_dispatch_total",
                           labels={"impl": "xla"}).value > 0


def test_the_convolution_carries_its_activation_and_no_swish_op_is_left():
    from paddle_tpu.models import qwen3_next

    main, _, _ = qwen3_next.build_train_program(
        qwen3_next.Qwen3NextConfig.from_dict(TOY), 1, 16)
    ops = main.global_block().ops
    convs = [op for op in ops if op.type == "causal_conv1d"]
    assert convs and all(op.attrs["activation"] == "swish" for op in convs)
    assert not [op for op in ops if op.type == "swish"]


def test_newest_step_regions_files_instructions_under_program_ops(
        both_sides):
    """What the benchmark's ``gdn_*`` / ``moe_*`` readers use after the
    step object is gone: the newest compiled step's instructions, each
    under (phase, program op type)."""
    found = set(both_sides[0]["regions"].values())
    for op_type in ("gated_delta_rule", "moe_experts", "rms_norm"):
        assert ("forward", op_type) in found, op_type
        assert ("backward", op_type) in found, op_type


def test_kernel_path_files_its_kernels_under_the_program_op(monkeypatch):
    """A step whose ``gated_delta_rule`` took the Pallas kernels (head dim
    128, the interpreter standing in for the chip): the forward kernel's
    instructions are filed under (forward, gated_delta_rule), the backward
    kernel's under (backward, gated_delta_rule) - where the ``gdn_*``
    metrics read them."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import monitor, profiler

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    S, Hk, Hv, d = 128, 1, 2, 128
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ins = [fluid.layers.data(n, shape) for n, shape in (
            ("q", [S, Hk, d]), ("k", [S, Hk, d]), ("v", [S, Hv, d]),
            ("a", [S, Hv]), ("b", [S, Hv]))]
        w = fluid.layers.create_parameter([d], "float32", name="v_scale")
        q, k, v, a, b = ins
        loss = fluid.layers.mean(fluid.layers.gated_delta_rule(
            q, k, v * w, a, b, chunk_size=64))
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {t.name: rng.randn(2, *t.shape[1:]).astype("float32")
            for t in ins}
    before = monitor.counter("gdn_dispatch_total",
                             labels={"impl": "pallas_bwd"}).value
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        regions = profiler.newest_step_regions()
        fn, specs = profiler._NEWEST_STEP
    assert monitor.counter("gdn_dispatch_total",
                           labels={"impl": "pallas_bwd"}).value > before
    op_names = profiler.op_names_of(fn.lower(*specs).compile().as_text())
    for kernel, phase in (("gdn_chunk_fwd", "forward"),
                          ("gdn_chunk_bwd", "backward")):
        filed = {regions[i] for i, name in op_names.items()
                 if "/%s/" % kernel in name and i in regions}
        assert filed == {(phase, "gated_delta_rule")}, (kernel, filed)
