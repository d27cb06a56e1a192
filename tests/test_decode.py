"""Autoregressive KV-cache decode fast path: ring-buffer cache updates,
cache-aware attention (masked-length fallback + Pallas decode tier), the
traced (prefill, decode) program pair, and the generative Predictor
routing. The load-bearing invariants: greedy decode through the cache is
TOKEN-IDENTICAL to full re-encode, and an N-token generation costs
exactly TWO executor compiles."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import monitor

pytestmark = pytest.mark.decode


# -- ring-buffer cache update ----------------------------------------------
def test_kv_cache_update_ring_wraparound():
    from paddle_tpu.kernels.attention import kv_cache_update

    B, H, C, d = 2, 1, 5, 3
    cache = np.zeros((B, H, C, d), np.float32)
    new = np.arange(B * H * d, dtype=np.float32).reshape(B, H, 1, d) + 1
    # slot = len % C: sequence 0 writes slot 0, sequence 1 (len 7) wraps
    # to slot 2
    lens = np.array([0, 7], np.int32)
    out, out_len = kv_cache_update(cache, new, lens)
    out = np.asarray(out)
    assert np.asarray(out_len).tolist() == [1, 8]
    assert (out[0, 0, 0] == new[0, 0, 0]).all()
    assert (out[1, 0, 2] == new[1, 0, 0]).all()
    assert out[0, 0, 1:].sum() == 0 and out[1, 0, 0:2].sum() == 0


def test_cache_attention_masked_slots_are_exactly_dead():
    """fp32-exact masking: garbage in slots beyond cache_len must not
    perturb the output by even one ulp."""
    from paddle_tpu.kernels.attention import attention_with_cache

    rng = np.random.RandomState(0)
    B, H, C, d, n = 2, 2, 8, 4, 5
    q = rng.randn(B, H, 1, d).astype(np.float32)
    k = rng.randn(B, H, C, d).astype(np.float32)
    v = rng.randn(B, H, C, d).astype(np.float32)
    lens = np.full((B,), n, np.int32)
    base = np.asarray(attention_with_cache(q, k, v, lens))
    k2, v2 = k.copy(), v.copy()
    k2[:, :, n:] = 1e9
    v2[:, :, n:] = -1e9
    poisoned = np.asarray(attention_with_cache(q, k2, v2, lens))
    assert (base == poisoned).all()


def test_cache_attention_matches_full_recompute():
    """Feeding tokens one at a time through the ring (including PAST the
    capacity) attends over exactly the last min(len, C) tokens — the
    same probabilities a full recompute over that window produces."""
    from paddle_tpu.kernels.attention import (attention_with_cache,
                                              kv_cache_update)

    rng = np.random.RandomState(1)
    B, H, C, d, steps = 1, 2, 4, 8, 7  # wraps the ring twice
    kc = np.zeros((B, H, C, d), np.float32)
    vc = np.zeros((B, H, C, d), np.float32)
    lens = np.zeros((B,), np.int32)
    ks = rng.randn(steps, B, H, 1, d).astype(np.float32)
    vs = rng.randn(steps, B, H, 1, d).astype(np.float32)
    qs = rng.randn(steps, B, H, 1, d).astype(np.float32)
    for t in range(steps):
        kc, new_len = kv_cache_update(kc, ks[t], lens)
        vc, _ = kv_cache_update(vc, vs[t], lens)
        lens = new_len
        got = np.asarray(attention_with_cache(qs[t], kc, vc, lens))
        lo = max(0, t + 1 - C)
        kw = np.concatenate(list(ks[lo:t + 1]), axis=2)
        vw = np.concatenate(list(vs[lo:t + 1]), axis=2)
        s = np.einsum("bhqd,bhkd->bhqk", qs[t], kw) / np.sqrt(d)
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        want = np.einsum("bhqk,bhkd->bhqd", w, vw)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert np.asarray(lens).tolist() == [steps]


@pytest.mark.parametrize("C", [7, 13, 128, 256])
def test_pallas_decode_kernel_matches_fallback(monkeypatch, C):
    """The capacity threshold patched to C + PALLAS_INTERPRET=1 exercises
    the Pallas decode tier on CPU — including prime/odd capacities, which
    take the pad-to-128 path."""
    from paddle_tpu.kernels import attention

    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(C)
    B, H, d = 2, 2, 8
    q = rng.randn(B, H, 1, d).astype(np.float32)
    k = rng.randn(B, H, C, d).astype(np.float32)
    v = rng.randn(B, H, C, d).astype(np.float32)
    # one partially-filled sequence, one wrapped past capacity
    lens = np.array([max(1, C // 2), C + 3], np.int32)
    want = np.asarray(attention.attention_with_cache(q, k, v, lens))
    assert not attention._use_decode_kernel(k)
    monkeypatch.setattr(attention, "_MAX_FUSED_SEQ", C)
    assert attention._use_decode_kernel(k)
    got = np.asarray(attention.attention_with_cache(q, k, v, lens))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


# -- traced (prefill, decode) pair ----------------------------------------
def test_greedy_decode_token_identical_and_two_traces():
    """THE acceptance pair: KV-cache greedy decode emits the same tokens
    as full re-encode decode from the same weights, and the whole
    N-token generation costs exactly two executor compiles (one
    prefill, one decode) — zero on a repeat generation."""
    from paddle_tpu.models.transformer import (Transformer,
                                               build_decode_session,
                                               make_causal_bias)

    B, S, P, C, NEW = 2, 6, 4, 16, 6
    with fluid.dygraph.guard():
        np.random.seed(0)
        model = Transformer.tiny()
        model.eval()
        sess = build_decode_session(model, B, S, P, C, end_id=1)

        rng = np.random.RandomState(7)
        src = rng.randint(2, 512, (B, S)).astype(np.int64)
        prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
        plens = np.full((B,), P, np.int64)

        m0 = monitor.counter("executor_compile_cache_miss_total").value
        steps0 = monitor.counter("decode_steps_total").value
        toks, fin = sess.generate(src, prompt, plens, NEW)
        m1 = monitor.counter("executor_compile_cache_miss_total").value
        assert m1 - m0 == 2, "want exactly (prefill, decode) compiles"
        assert monitor.counter("decode_steps_total").value - steps0 \
            == NEW - 1
        assert toks.shape == (B, NEW) and fin.shape == (B,)

        toks2, _ = sess.generate(src, prompt, plens, NEW)
        assert monitor.counter(
            "executor_compile_cache_miss_total").value == m1, \
            "repeat generation retraced"
        assert (toks == toks2).all()

        # full re-encode greedy baseline off the SAME eager weights
        def var(x):
            return fluid.dygraph.to_variable(x)

        cur = prompt.copy()
        base = []
        pos_src = np.tile(np.arange(S, dtype=np.int64), (B, 1))
        for _ in range(NEW):
            T = cur.shape[1]
            pos = np.tile(np.arange(T, dtype=np.int64), (B, 1))
            logits = model(var(src), var(cur), var(pos_src), var(pos),
                           var(make_causal_bias(T)))
            nxt = np.asarray(logits._ivar)[:, -1, :].argmax(-1)
            base.append(nxt)
            cur = np.concatenate([cur, nxt[:, None].astype(np.int64)],
                                 axis=1)
        assert (toks == np.stack(base, axis=1)).all(), (
            toks.tolist(), [b.tolist() for b in base])


def test_decode_session_validates_inputs():
    from paddle_tpu.models.transformer import (Transformer,
                                               build_decode_session)

    with fluid.dygraph.guard():
        model = Transformer.tiny()
        with pytest.raises(ValueError, match="ring boundary"):
            build_decode_session(model, 1, 4, 8, cache_capacity=4)
        sess = build_decode_session(model, 1, 4, 2, cache_capacity=8)
        src = np.zeros((1, 4), np.int64)
        with pytest.raises(ValueError, match="shape mismatch"):
            sess.generate(src, np.zeros((1, 3), np.int64), [2], 2)
        with pytest.raises(ValueError, match="prompt_lens"):
            sess.generate(src, np.zeros((1, 2), np.int64), [3], 2)
        with pytest.raises(ValueError, match="max_new_tokens"):
            sess.generate(src, np.zeros((1, 2), np.int64), [2], 0)


# -- seq2seq encoder hoist --------------------------------------------------
def test_seq2seq_split_infer_bit_identical():
    """The encoder hoisted out of beam search (encoder program once +
    decode-from-state program) reproduces the monolithic infer program
    BIT-identically from the same trained scope."""
    from paddle_tpu.models import seq2seq

    rng = np.random.RandomState(0)
    V, L = 16, 5
    main, startup, loss = seq2seq.build_train_program(
        src_vocab=V, tgt_vocab=V, src_len=L, tgt_len=L, lr=1e-2)
    infer, _, seqs = seq2seq.build_infer_program(
        src_vocab=V, tgt_vocab=V, src_len=L, max_tgt_len=L, beam_size=3)
    enc_p, _, enc_state = seq2seq.build_encoder_program(
        src_vocab=V, src_len=L)
    dec_p, _, seqs2 = seq2seq.build_decode_program(
        tgt_vocab=V, max_tgt_len=L, beam_size=3)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(20):
            feed = seq2seq.synthetic_pairs(rng, 16, V, L)
            exe.run(main, feed=feed, fetch_list=[loss])
        feed = seq2seq.synthetic_pairs(rng, 4, V, L)
        (sv,) = exe.run(infer, feed={"s2s_src": feed["s2s_src"]},
                        fetch_list=[seqs])
        sv2 = seq2seq.run_split_infer(exe, scope, enc_p, enc_state,
                                      dec_p, seqs2, feed["s2s_src"])
    assert (np.asarray(sv) == np.asarray(sv2)).all()


# -- generative Predictor routing ------------------------------------------
def test_generative_predictor_no_shape_recompiles():
    """Growing output length through the plain Predictor re-feeds a
    longer sequence every call (a recompile per length). The decode
    routing is shape-closed: one prefill + one decode compile serve
    every max_new_tokens, and predictor_shape_recompile_total stays 0."""
    from paddle_tpu import inference
    from paddle_tpu.models.transformer import Transformer

    with fluid.dygraph.guard():
        model = Transformer.tiny()
        p = inference.GenerativePredictor(
            model, batch_size=1, src_len=6, prompt_len=4,
            cache_capacity=32, end_id=1)
    rng = np.random.RandomState(3)
    feed = {"src": rng.randint(2, 512, (1, 6)).astype(np.int64),
            "prompt": rng.randint(2, 512, (1, 4)).astype(np.int64)}
    rec0 = monitor.counter("predictor_shape_recompile_total").value
    m0 = monitor.counter("executor_compile_cache_miss_total").value
    outs = [p.run(feed, max_new_tokens=n)[0] for n in (2, 5, 9)]
    m1 = monitor.counter("executor_compile_cache_miss_total").value
    assert m1 - m0 == 2, (
        "generative serving cost %d compiles for 3 growing-length "
        "requests, want 2 (one prefill + one decode)" % (m1 - m0))
    assert monitor.counter(
        "predictor_shape_recompile_total").value == rec0
    assert [o.shape for o in outs] == [(1, 2), (1, 5), (1, 9)]
    # growing max_new_tokens extends, never rewrites, the trajectory
    assert (outs[2][:, :5] == outs[1]).all()
    assert (outs[1][:, :2] == outs[0]).all()
    assert p.get_input_names() == ["src", "prompt", "prompt_lens"]
    with pytest.raises(ValueError, match="missing generative feeds"):
        p.run({"src": feed["src"]}, max_new_tokens=2)
