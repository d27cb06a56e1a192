#!/usr/bin/env python3
"""Chip smoke: the quickest proof that this tree still starts on the TPU.

One process drives the two paths users enter — ``fluid.Executor.run`` on
a training Program, and ``inference.GenerativePredictor`` ->
``inference.GenerativeServer`` — at the full width of models the repo
has (depth as published too: BERT-base, Transformer-big), with random
weights from a seed, and checks what comes out by the repo's own means.
It times nothing as a metric: the seconds it prints are compile + run of
a correctness check, not a rate.

    python chip_smoke.py                      # every phase, on the chip
    python chip_smoke.py multichip            # named phases only
    python chip_smoke.py --rehearse-cpu ...   # toy sizes, Pallas interpreter

Without ``--rehearse-cpu`` it refuses to run anywhere but on a ``tpu``
platform with compiled Pallas kernels: there is no CPU branch at real
size. Every phase runs unguarded — an exception or a failed assertion
ends the process non-zero. It spawns no process (a chip belongs to one),
needs no network and reads no file git does not track. The last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it.
"""

import concurrent.futures
import copy
import functools
import importlib.metadata
import json
import math
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

_HEAD_DIM = 64          # every model in the repo uses d=64 heads


def _real_sizes():
    """What each phase runs on the chip."""
    from paddle_tpu.models import bert, transformer

    return types.SimpleNamespace(
        rehearsal=False,
        bert_cfg=bert.BertConfig.base,
        lr=1e-4,                                    # as bench_bert
        # (seq_len, batch, steps, Pallas tiers the step must contain):
        # s=128 takes XLA's einsum chain (models/bert.py "auto"); s=512
        # is the first shape on the batch-blocked kernel WITH in-kernel
        # PRNG dropout
        train_runs=((128, 128, 8, ()),
                    (512, 32, 3, ("block", "block_bwd"))),
        # (S, B, H, forward tier): every tier _fused can reach
        fused_cases=((512, 8, 12, "block"), (2048, 2, 12, "long"),
                     (4096, 1, 12, "flash"), (8192, 1, 12, "flash")),
        dropout_kernels=True,
        decode_case=(8, 16, 1024),                  # B, H, capacity
        paged_pages=(16, 128),
        # B, S, Hk, Hv, d: one row of qwen3-next-80b-a3b.train-s8192
        delta_rule_case=(1, 8192, 16, 32, 128),
        # B, S, C: the convolution of qwen3-next-80b-a3b.train-s8192 and
        # of kimi-linear-48b-a3b.train-s16384
        conv_cases=((2, 8192, 8192), (1, 16384, 12288)),
        transformer=transformer.Transformer.big,
        vocab=32000,
        serve=dict(batch_size=8, src_len=128, prompt_len=64,
                   cache_capacity=1024),
        serve_kernels=True,
        multichip_run=(128, 128, 4))                # seq_len, batch, steps


def _toy_sizes():
    """The CPU rehearsal: it exists to find typos before chip time is
    spent, and proves nothing about the chip."""
    from paddle_tpu.models import bert, transformer

    return types.SimpleNamespace(
        rehearsal=True,
        bert_cfg=bert.BertConfig.tiny,
        lr=1e-2,        # a few tiny noisy batches must still descend
        train_runs=((16, 8, 6, ()), (32, 4, 6, ())),
        fused_cases=((128, 2, 2, "block"), (2048, 1, 1, "long")),
        # the TPU PRNG has no interpreter lowering: dropout falls back
        dropout_kernels=False,
        decode_case=(2, 2, 1024),
        paged_pages=(128,),
        delta_rule_case=(1, 256, 1, 2, 128),
        conv_cases=((2, 512, 256),),
        transformer=transformer.Transformer.tiny,
        vocab=512,
        serve=dict(batch_size=4, src_len=8, prompt_len=4,
                   cache_capacity=16),
        serve_kernels=False,                        # capacity < 1024
        multichip_run=(16, 8, 4))


# -- shared helpers -----------------------------------------------------------
def _compile_misses():
    from paddle_tpu.fluid import monitor

    return monitor.counter("executor_compile_cache_miss_total").value


def _kernel_traces():
    """{tier: count} of the trace-time Pallas dispatch counter."""
    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels.attention import KERNEL_TIERS

    return {t: monitor.counter("attn_kernel_dispatch_total",
                               labels={"tier": t}).value
            for t in KERNEL_TIERS}


def _traced_since(before, tiers):
    """Assert every tier in ``tiers`` was traced since ``before``."""
    now = _kernel_traces()
    missing = [t for t in tiers if now[t] <= before[t]]
    assert not missing, (
        "Pallas tier(s) %r never traced — the dispatch took the jnp "
        "fallback (counters before %r, after %r)" % (missing, before, now))


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "non-finite values in kernel output"
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _sig(x):
    return float("%.2g" % x)


def _l2(x):
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _dot(a, b):
    return float(np.sum(np.asarray(a, np.float64) * np.asarray(b, np.float64)))


# -- train --------------------------------------------------------------------
def phase_train(sz):
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    platform = jax.devices()[0].platform
    cfg = sz.bert_cfg()
    runs = []
    for seq_len, batch, steps, tiers in sz.train_runs:
        main, startup, loss = bert.build_pretrain_program(
            cfg, seq_len=seq_len, lr=sz.lr, use_amp=True)
        exe = fluid.Executor()
        feed = {k: jax.device_put(v) for k, v in
                bert.synthetic_batch(cfg, batch, seq_len).items()}
        scope = fluid.Scope()
        traced0 = _kernel_traces()
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup)
            for i in range(steps):
                (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                                return_numpy=False)
                losses.append(float(np.asarray(lv).ravel()[0]))
                if i == 0:
                    misses = _compile_misses()
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0], (
            "loss did not decrease at s=%d: %r" % (seq_len, losses))
        assert _compile_misses() == misses, (
            "steps after the first recompiled at s=%d" % seq_len)
        param = scope.find_var("word_emb")
        for what, arr in (("fetched loss", lv), ("word_emb", param)):
            assert {d.platform for d in arr.devices()} == {platform}, (
                "%s lives on %r, not on %s" % (what, arr.devices(),
                                               platform))
        _traced_since(traced0, tiers)
        runs.append({"seq_len": seq_len, "batch": batch, "steps": steps,
                     "loss_first": round(losses[0], 4),
                     "loss_last": round(losses[-1], 4),
                     "pallas_tiers": list(tiers)})
    return {"model": "bert %dx%d" % (cfg.n_layers, cfg.hidden),
            "runs": runs,
            "asserted": "loss finite each step and last < first; no "
                        "compile after step 1; loss and word_emb on the "
                        "%s; named Pallas tiers traced" % platform}


# -- kernels ------------------------------------------------------------------
# Max-normalised error allowed against the f32 oracle. The chip's default
# f32 dot is ONE bf16 pass, in Mosaic as in XLA, so f32 operands land near
# 1e-2 (0.014 at S=2048, chip run PR 21); bf16 operands are exact in the
# oracle and land near 5e-3.
_TOL = 3e-2
_MASK_TOL = 5e-2


def _fused_oracle(A, q, k, v, w, bias):
    """The module's f32 reference and its gradients, over head chunks so
    the [S, S] scores of the long cases fit beside everything else."""
    import jax
    import jax.numpy as jnp

    B, H, S, d = q.shape
    hc = max(1, min(H, (1 << 28) // (B * S * S)))
    while H % hc:
        hc -= 1
    scale = 1.0 / math.sqrt(d)

    @jax.jit
    def chunk(q, k, v, w, bias):
        def loss(q, k, v):
            o = A._ref_attention(q, k, v, bias, scale, 0.0, None)
            return jnp.sum(o * w), o

        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o,) + grads

    parts = []
    with jax.default_matmul_precision("highest"):
        for h in range(0, H, hc):
            parts.append([np.asarray(x) for x in chunk(
                *(t[:, h:h + hc].astype(jnp.float32)
                  for t in (q, k, v, w)), bias)])
    return [np.concatenate(xs, axis=1) for xs in zip(*parts)]


def _check_fused(A, S, B, H, dtype, tier, dropout_kernels):
    """fused_attention forward + gradients at one shape: dropout 0
    against the oracle; dropout 0.1 finite, bit-identical across two
    calls with the same seed, and its three masks (forward, dq, dk/dv)
    shown to be one mask by two identities that hold only then:
    o is linear in v, so <dv, v> = loss; scores see only q·kᵀ, so
    <dq, q> = <dk, k>."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(S)
    q, k, v, w = (jnp.asarray(rng.randn(B, H, S, _HEAD_DIM), dtype)
                  for _ in range(4))
    bias = np.zeros((B, 1, 1, S), np.float32)
    bias[..., S - S // 8:] = -1e4           # a padded tail, as BERT masks
    bias = jnp.asarray(bias)
    key = jax.random.PRNGKey(S)

    # w and bias ride as arguments: closed over, they would be baked
    # into every executable (25 MB apiece at S=8192) and into the cache
    def loss(q, k, v, w, bias, p_drop):
        o = A.fused_attention(q, k, v, bias, dropout_prob=p_drop,
                              rng_key=key)
        return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o

    def grad_fn(p_drop):
        return jax.jit(jax.value_and_grad(
            functools.partial(loss, p_drop=p_drop), argnums=(0, 1, 2),
            has_aux=True))

    traced0 = _kernel_traces()
    (_, o), grads = grad_fn(0.0)(q, k, v, w, bias)
    _traced_since(traced0, (tier, tier + "_bwd"))
    errs = [_rel_err(got, want) for got, want in
            zip((o,) + grads, _fused_oracle(A, q, k, v, w, bias))]
    assert max(errs) < _TOL, (
        "fused_attention S=%d %s vs f32 oracle: o/dq/dk/dv errors %r"
        % (S, np.dtype(dtype).name, errs))

    traced0 = _kernel_traces()
    f = grad_fn(0.1)
    (l1, o1), g1 = f(q, k, v, w, bias)
    (l2, o2), g2 = f(q, k, v, w, bias)
    if dropout_kernels:
        _traced_since(traced0, (tier, tier + "_bwd"))
    for a, b in zip((o1,) + g1, (o2,) + g2):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a.astype(np.float32)).all()
        assert a.tobytes() == b.tobytes(), (
            "dropout S=%d: two calls with one seed differ" % S)
    assert not np.array_equal(np.asarray(o1), np.asarray(o)), (
        "dropout S=%d changed nothing" % S)
    dq, dk, dv = g1
    ow = np.asarray(o1, np.float64) * np.asarray(w, np.float64)
    mask_fwd = abs(_dot(dv, v) - float(l1)) / _l2(ow)
    mask_bwd = abs(_dot(dq, q) - _dot(dk, k)) / _l2(
        np.asarray(dq, np.float64) * np.asarray(q, np.float64))
    assert max(mask_fwd, mask_bwd) < _MASK_TOL, (
        "dropout S=%d: forward/backward masks disagree (<dv,v> vs loss "
        "%.3g, <dq,q> vs <dk,k> %.3g)" % (S, mask_fwd, mask_bwd))
    return {"S": S, "dtype": np.dtype(dtype).name, "tier": tier,
            "err": _sig(max(errs)),
            "mask_err": _sig(max(mask_fwd, mask_bwd))}


def _check_decode(A, B, H, C, Q, dtype):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(C + Q)
    q = jnp.asarray(rng.randn(B, H, Q, _HEAD_DIM), dtype)
    kc, vc = (jnp.asarray(rng.randn(B, H, C, _HEAD_DIM), dtype)
              for _ in range(2))
    lens = jnp.asarray(np.linspace(Q, C, B).astype(np.int32))
    window = Q > 1
    traced0 = _kernel_traces()
    got = jax.jit(functools.partial(
        A.attention_with_cache, causal_window=window))(q, kc, vc, lens)
    _traced_since(traced0, ("decode",))
    with jax.default_matmul_precision("highest"):
        want = A._ref_attention_cache(
            q.astype(jnp.float32), kc.astype(jnp.float32),
            vc.astype(jnp.float32), lens, 1.0 / math.sqrt(_HEAD_DIM),
            causal_window=window)
    err = _rel_err(got, want)
    assert err < _TOL, ("attention_with_cache C=%d Q=%d %s: %g"
                        % (C, Q, np.dtype(dtype).name, err))
    return {"C": C, "Q": Q, "dtype": np.dtype(dtype).name,
            "tier": "decode", "err": _sig(err)}


def _check_paged(A, B, H, cap, ptok, dtype):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(cap + ptok)
    npages = cap // ptok
    P = B * npages + 1
    q = jnp.asarray(rng.randn(B, H, 1, _HEAD_DIM), dtype)
    kp, vp = (jnp.asarray(rng.randn(P, H, ptok, _HEAD_DIM), dtype)
              for _ in range(2))
    # every slot's pages scattered over the pool; page 0 is scratch
    table = jnp.asarray((rng.permutation(P - 1) + 1)
                        .reshape(B, npages).astype(np.int32))
    lens = jnp.asarray(np.linspace(1, cap, B).astype(np.int32))
    traced0 = _kernel_traces()
    got = jax.jit(A.paged_attention_cache)(q, kp, vp, table, lens)
    _traced_since(traced0, ("paged",))
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want = A._ref_attention_cache(
            q.astype(f32), A.gather_paged_cache(kp.astype(f32), table),
            A.gather_paged_cache(vp.astype(f32), table), lens,
            1.0 / math.sqrt(_HEAD_DIM))
    err = _rel_err(got, want)
    assert err < _TOL, ("paged_attention_cache cap=%d ptok=%d %s: %g"
                        % (cap, ptok, np.dtype(dtype).name, err))
    return {"capacity": cap, "page_tokens": ptok,
            "dtype": np.dtype(dtype).name, "tier": "paged",
            "err": _sig(err)}


def _check_delta_rule(B, S, Hk, Hv, d, chunk=64):
    """The gated delta rule's kernels (kernels/delta_rule.py) in bf16,
    forward and the five cotangents, against the f32 position-by-position
    recurrence. The recurrence's backward keeps a state a position, so it
    runs on the first key head and its value heads only; heads do not mix,
    so those heads' cotangents are the whole comparison for them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels import delta_rule

    def traced():
        return {i: monitor.counter("gdn_dispatch_total",
                                   labels={"impl": i}).value
                for i in ("pallas", "pallas_bwd")}

    ks = jax.random.split(jax.random.PRNGKey(29), 5)
    q = jax.random.normal(ks[0], (B, S, Hk, d))
    k = jax.random.normal(ks[1], (B, S, Hk, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, Hv, d))
    g = -0.5 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv)))
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    rep = Hv // Hk

    def recurrence(q, k, v, g, beta):      # one key head, f32
        def step(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            state = state * jnp.exp(g_t)[..., None, None]
            delta = b_t[..., None] * (
                v_t - jnp.einsum("bhkv,bk->bhv", state, k_t))
            state = state + k_t[:, None, :, None] * delta[..., None, :]
            return state, jnp.einsum("bhkv,bk->bhv", state, q_t)
        xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                   for t in (q[:, :, 0], k[:, :, 0], v, g, beta))
        _, o = jax.lax.scan(step, jnp.zeros((B, rep, d, d)), xs)
        return jnp.moveaxis(o, 0, 1)

    def run(fn, *args):
        def loss(*a):
            o = fn(*a).astype(jnp.float32)
            return jnp.sum(jnp.sin(4.0 * o)), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
        return (o,) + grads

    before = traced()
    got = run(lambda *a: delta_rule.gated_delta_rule_pallas(
        *a, chunk_size=chunk), q, k, v, g, beta)
    missing = [i for i, n in traced().items() if n <= before[i]]
    assert not missing, "delta rule: never traced %r" % missing
    with jax.default_matmul_precision("highest"):
        want = run(recurrence, q[:, :, :1], k[:, :, :1], v[:, :, :rep],
                   g[:, :, :rep], beta[:, :, :rep])
    # o, dq, dk, dv, dg, dbeta: the first key head's share of each
    heads = (rep, 1, 1, rep, rep, rep)
    errs = [_rel_err(a[:, :, :h], b) for a, b, h in zip(got, want, heads)]
    assert max(errs) < _TOL, (
        "gated_delta_rule pallas S=%d chunk=%d: o dq dk dv dg dbeta %r"
        % (S, chunk, errs))
    return {"S": S, "B": B, "heads": [Hk, Hv], "dtype": "bfloat16",
            "tier": "gdn_pallas", "err": _sig(max(errs))}


def _check_conv(B, S, C, K=4):
    """The short convolution's kernels (kernels/causal_conv.py) in bf16
    with the SiLU inside, forward, dx and dw, against the XLA form of the
    same equations (``fluid/ops/linear_attention.py``); the largest
    difference of each is reported."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid import monitor
    from paddle_tpu.fluid.ops import linear_attention

    def traced():
        return {i: monitor.counter("conv_dispatch_total",
                                   labels={"impl": i}).value
                for i in ("pallas", "pallas_bwd")}

    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    x = jax.random.normal(ks[0], (B, S, C)).astype(jnp.bfloat16)
    w = (0.5 * jax.random.normal(ks[1], (C, K))).astype(jnp.bfloat16)
    dy = jax.random.normal(ks[2], (B, S, C)).astype(jnp.bfloat16)

    def run(conv):
        y, vjp = jax.vjp(conv, x, w)
        return (y,) + vjp(dy)

    before = traced()
    got = jax.jit(lambda: run(lambda x, w: linear_attention.causal_conv(
        x, w, "swish")))()
    missing = [i for i, n in traced().items() if n <= before[i]]
    assert not missing, "causal conv: never traced %r" % missing
    want = jax.jit(lambda: run(linear_attention._causal_conv(True)))()
    diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32))))
             for a, b in zip(got, want)]
    errs = [_rel_err(a, b) for a, b in zip(got, want)]
    assert max(errs) < _TOL, (
        "causal_conv pallas B=%d S=%d C=%d: y dx dw %r" % (B, S, C, errs))
    return {"S": S, "B": B, "C": C, "dtype": "bfloat16",
            "tier": "conv_pallas", "err": _sig(max(errs)),
            "max_diff_y_dx_dw": [_sig(d) for d in diffs]}


def phase_kernels(sz):
    import jax.numpy as jnp

    from paddle_tpu.kernels import attention as A

    cases = [_check_delta_rule(*sz.delta_rule_case)]
    cases += [_check_conv(*case) for case in sz.conv_cases]
    B, H, C = sz.decode_case
    for dtype in (jnp.float32, jnp.bfloat16):
        for S, b, h, tier in sz.fused_cases:
            cases.append(_check_fused(A, S, b, h, dtype, tier,
                                      sz.dropout_kernels))
        for Q in (1, 5):
            cases.append(_check_decode(A, B, H, C, Q, dtype))
        for ptok in sz.paged_pages:
            cases.append(_check_paged(A, B, H, C, ptok, dtype))
    return {"cases": cases,
            "asserted": "each case traced its Pallas tier (no fallback) "
                        "and is within %g of the f32 oracle, max-"
                        "normalised; fused: forward + dq/dk/dv, dropout "
                        "0.1 bit-identical across two calls with one "
                        "mask in forward and backward" % _TOL}


# -- serve --------------------------------------------------------------------
def phase_serve(sz):
    """Token identity between the paged server and the dense session is
    exact where both run the same arithmetic (the CPU tests). At the
    chip's default matmul precision an f32 dot is one bf16 pass, and
    the paged and dense decode kernels round their probabilities under
    different running maxima: 1 token of 80 flipped there (chip run,
    PR 21). So the engines are compared at "highest", where identical
    tokens are a fair demand — set process-wide, since the server's
    worker thread is the one that traces the decode program. The
    kernels phase holds the default precision against the oracle."""
    import jax

    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        return _serve(sz)
    finally:
        jax.config.update("jax_default_matmul_precision", prev)


def _serve(sz):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.inference import (Closed, GenerativePredictor,
                                      GenerativeServer)
    from paddle_tpu.models.transformer import build_decode_session

    geo = sz.serve
    B, S, P = geo["batch_size"], geo["src_len"], geo["prompt_len"]
    rng = np.random.RandomState(0)
    src = rng.randint(2, sz.vocab, (B, S)).astype(np.int64)
    prompt = rng.randint(2, sz.vocab, (B, P)).astype(np.int64)
    # one request per slot, no two alike in prompt length or budget
    plens = np.array([1 + (i * (P - 1)) // (B - 1) for i in range(B)],
                     np.int64)
    budgets = [3 + 2 * i for i in range(B)]
    traced0 = _kernel_traces()
    with fluid.dygraph.guard():
        model = sz.transformer()
        m0 = _compile_misses()
        pred = GenerativePredictor(model, paged=True, **geo)
        server = GenerativeServer(pred.open_stream())

        def client(ids):
            return [(i, server.submit(src[i], prompt[i],
                                      prompt_len=int(plens[i]),
                                      max_new_tokens=budgets[i]))
                    for i in ids]

        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            submitted = sum(pool.map(client, (range(0, B, 2),
                                              range(1, B, 2))), [])
        served = {i: fut.result(timeout=900) for i, fut in submitted}
        server.close()
        try:
            server.submit(src[0], prompt[0])
        except Closed:
            pass
        else:
            raise AssertionError("server accepted a request after close()")
        m1 = _compile_misses()
        assert m1 - m0 == 2, (
            "paged serving cost %d compiles, want 2 (batch-1 prefill + "
            "paged decode)" % (m1 - m0))
        dense = build_decode_session(model, end_id=1, **geo)
        base, _ = dense.generate(src, prompt, plens, max(budgets))
        assert _compile_misses() - m1 == 2, (
            "dense session cost %d compiles, want 2 (prefill + decode)"
            % (_compile_misses() - m1))
    assert len(served) == B
    n_tokens = 0
    for i in range(B):
        toks, finished = served[i]
        toks = np.asarray(toks)
        assert 1 <= toks.size <= budgets[i]
        assert finished or toks.size == budgets[i], (
            "request %d retired early: %d of %d tokens, unfinished"
            % (i, toks.size, budgets[i]))
        want = np.asarray(base[i])[:toks.size]
        assert np.array_equal(toks, want), (
            "request %d (prompt_len %d): paged server and dense session "
            "part at token %d of %d: %r vs %r"
            % (i, plens[i], int(np.argmax(toks != want)), toks.size,
               toks.tolist(), want.tolist()))
        n_tokens += int(toks.size)
    if sz.serve_kernels:
        _traced_since(traced0, ("paged", "decode"))
    return {"model": "transformer %d+%dx%d" % (
                len(model.enc_layers), len(model.dec_layers),
                model.d_model),
            "requests": B, "tokens": n_tokens, "geometry": geo,
            "asserted": "every future resolved from two client threads; "
                        "server closed and refused the next submit; 2 "
                        "compiles paged + 2 dense; tokens identical to "
                        "the dense session at matmul precision "
                        "'highest'%s" % (
                            "; paged and decode Pallas kernels traced"
                            if sz.serve_kernels else "")}


# -- multichip ----------------------------------------------------------------
def phase_multichip(sz):
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    devices = jax.devices()
    if len(devices) < 4:
        return {"multichip": "not run", "devices": len(devices)}
    devices = devices[:4]
    seq_len, batch, steps = sz.multichip_run
    cfg = sz.bert_cfg()
    # dropout off: the layouts must reproduce one trajectory
    cfg.hidden_dropout = cfg.attn_dropout = 0.0
    batch_np = bert.synthetic_batch(cfg, batch, seq_len)

    def run(tp_axis=None, **strategy):
        c = copy.copy(cfg)
        c.tp_axis = tp_axis
        main, startup, loss = bert.build_pretrain_program(
            c, seq_len=seq_len, lr=sz.lr, use_amp=True)
        feed, prog = batch_np, main
        if strategy:
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, places=devices, **strategy)
            # staged as DeviceStager stages them: by the strategy's own
            # feed layout, one batch shard a device
            feed = {k: jax.device_put(v, prog.feed_sharding(v, name=k))
                    for k, v in batch_np.items()}
            for name, arr in feed.items():
                rows = {s.data.shape[0] for s in arr.addressable_shards}
                assert len(arr.sharding.device_set) == 4 and \
                    rows == {batch // prog.mesh.shape["dp"]}, (
                        "feed %s not sharded over the mesh: %r"
                        % (name, arr.sharding))
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[loss])[0]).ravel()[0])
                for _ in range(steps)]
        assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
        return losses, scope

    single, _ = run()
    dp, _ = run(mesh_axes=("dp",))
    dptp, scope = run(tp_axis="tp", mesh_axes=("dp", "tp"),
                      mesh_shape={"dp": 2, "tp": 2})
    for name, got in (("dp=4", dp), ("dp=2 x tp=2", dptp)):
        assert np.allclose(got, single, rtol=5e-3, atol=0), (
            "%s losses %r part from the single-chip %r"
            % (name, got, single))
    # tensor parallelism is real: some parameter lives as 1/tp shards
    split = [n for n, v in scope.vars.items()
             if hasattr(v, "addressable_shards")
             and v.addressable_shards[0].data.shape != v.shape]
    assert split, "no parameter is sharded over tp"
    in_use = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None and sz.rehearsal:
            continue                    # the CPU backend reports none
        assert stats["bytes_in_use"] > 0, "%r holds nothing" % (d,)
        in_use.append(stats["bytes_in_use"])
    return {"devices": len(devices), "seq_len": seq_len, "batch": batch,
            "loss_single": [round(x, 4) for x in single],
            "loss_dp4": [round(x, 4) for x in dp],
            "loss_dp2_tp2": [round(x, 4) for x in dptp],
            "tp_sharded_params": len(split), "bytes_in_use": in_use,
            "asserted": "feeds one batch shard a device; losses finite, "
                        "decreasing and within 5e-3 of the single-chip "
                        "trajectory at one seed and global batch, "
                        "dropout off; parameters split over tp; live "
                        "bytes on each of the four devices"}


# -- entry --------------------------------------------------------------------
PHASES = {"train": phase_train, "kernels": phase_kernels,
          "serve": phase_serve, "multichip": phase_multichip}


def main(argv):
    flags = [a for a in argv if a.startswith("-")]
    names = [a for a in argv if not a.startswith("-")]
    if set(flags) - {"--rehearse-cpu"} or set(names) - set(PHASES):
        sys.exit("usage: chip_smoke.py [--rehearse-cpu] [%s ...]"
                 % " | ".join(PHASES))
    rehearse = "--rehearse-cpu" in flags
    names = [p for p in PHASES if p in names] or list(PHASES)

    import jax

    from paddle_tpu.fluid import compile_cache

    # the rehearsal keeps its executables out of the checkout
    cache_dir = None if rehearse else compile_cache.use_jax_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("platform=%s%s device_kind=%s devices=%d"
          % (dev.platform, " rehearsal" if rehearse else "",
             dev.device_kind, device["count"]), flush=True)
    print("versions: " + " ".join(
        "%s=%s" % (p, importlib.metadata.version(p))
        for p in ("jax", "jaxlib", "libtpu")), flush=True)
    print("compilation cache: %s" % (cache_dir or "off (rehearsal)"),
          flush=True)

    interpret = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    if rehearse:
        if dev.platform != "cpu":
            sys.exit("chip_smoke: --rehearse-cpu is for the CPU; found "
                     "platform=%s" % dev.platform)
        # the one switch the kernels module reads; the rehearsal is the
        # only run that may set it
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        sz = _toy_sizes()
    else:
        found = []
        if dev.platform != "tpu":
            found.append("platform=%s (%s)" % (dev.platform,
                                               dev.device_kind))
        if interpret is not None:
            found.append("PADDLE_TPU_PALLAS_INTERPRET=%s is set"
                         % interpret)
        if found:
            sys.exit("chip_smoke: refusing to run: %s; this script "
                     "proves the tree starts on a tpu with compiled "
                     "kernels and has no other branch at real size"
                     % "; ".join(found))
        sz = _real_sizes()

    t_all = time.perf_counter()
    for name in names:
        t0 = time.perf_counter()
        line = {"phase": name}
        line.update(PHASES[name](sz))
        line["seconds"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(line), flush=True)
    print(json.dumps({"phases": names, "rehearsal": rehearse,
                      "seconds": round(time.perf_counter() - t_all, 1)}),
          flush=True)
    if not rehearse:
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
