"""Paged decode engine: shared KV block pool + per-slot page tables,
prefix caching with copy-on-write page aliasing, and speculative
draft/verify decoding. The load-bearing invariants: every engine emits
tokens BIT-IDENTICAL to the dense ring-cache baseline (ring wraparound
and post-hit COW divergence included), pool exhaustion sheds with the
typed ``Overloaded`` BEFORE any device work, and the speculative tier
costs exactly TWO extra compiles."""

import math

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import monitor
from paddle_tpu.fluid.resilience import Overloaded
from paddle_tpu.models.transformer import (Transformer,
                                           build_decode_session,
                                           build_paged_decode_session,
                                           build_speculative_session)

pytestmark = pytest.mark.decode


def _cm():
    return monitor.counter("executor_compile_cache_miss_total").value


def _drain(paged, out):
    """step() until every slot retires, collecting {slot: tokens}."""
    while paged.active_count:
        for slot, toks, fin in paged.step():
            out[slot] = (np.asarray(toks), bool(fin))
    return out


# -- token identity: paged ≡ dense -----------------------------------------
def test_paged_session_token_identical_to_dense():
    B, S, P, C = 3, 6, 4, 16
    rng = np.random.RandomState(0)
    src = rng.randint(2, 512, (B, S)).astype(np.int64)
    prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
    plens = np.array([4, 3, 2], np.int64)
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        dense = build_decode_session(model, B, S, P, C, end_id=1)
        base, _ = dense.generate(src, prompt, plens, 6)
        paged = build_paged_decode_session(model, B, S, P, C, end_id=1,
                                           page_tokens=4)
        m0 = _cm()
        done = {}
        for b in range(B):
            slot, ready = paged.join(src[b], prompt[b],
                                     prompt_len=int(plens[b]),
                                     max_new_tokens=6)
            assert slot == b          # vacant slots fill in order
            if ready is not None:
                done[slot] = (np.asarray(ready[0]), bool(ready[1]))
        _drain(paged, done)
        m1 = _cm()
    assert m1 - m0 == 2, (
        "paged engine cost %d compiles, want 2 (batch-1 prefill + "
        "paged decode)" % (m1 - m0))
    for b in range(B):
        toks = done[b][0]
        assert np.array_equal(toks, np.asarray(base[b])[:toks.size]), (
            "slot %d: paged tokens diverged from dense" % b)
    # every page went back to the free list at retire
    assert paged.pool.live_pages == 0


def test_paged_ring_wraparound_token_identical():
    """Decode far enough past capacity that every ring position (so
    every page) is overwritten — the `pos % C` write path through the
    table must match the dense ring exactly."""
    B, S, P, C = 1, 6, 4, 8
    rng = np.random.RandomState(1)
    src = rng.randint(2, 512, (B, S)).astype(np.int64)
    prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
    new = 10                  # writes positions 4..13: wraps, covers C
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        dense = build_decode_session(model, B, S, P, C, end_id=1)
        base, _ = dense.generate(src, prompt,
                                 np.array([4], np.int64), new)
        paged = build_paged_decode_session(model, B, S, P, C, end_id=1,
                                           page_tokens=2)
        done = {}
        slot, ready = paged.join(src[0], prompt[0], max_new_tokens=new)
        if ready is not None:
            done[slot] = (np.asarray(ready[0]), bool(ready[1]))
        _drain(paged, done)
    toks = done[0][0]
    assert np.array_equal(toks, np.asarray(base[0])[:toks.size]), (
        "wraparound paged tokens diverged from dense")


# -- prefix caching + copy-on-write ----------------------------------------
def test_prefix_hit_aliases_pages_and_cow_diverges():
    """Second join of the same prompt must HIT (no prefill dispatch),
    alias the cached pages, and still decode the exact dense tokens —
    including past the ring wrap, where BOTH slots copy-on-write the
    shared prompt page before overwriting it."""
    B, S, P, C = 2, 6, 4, 8
    rng = np.random.RandomState(2)
    src = rng.randint(2, 512, (S,)).astype(np.int64)
    prompt = rng.randint(2, 512, (P,)).astype(np.int64)
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        dense = build_decode_session(model, B, S, P, C, end_id=1)
        base, _ = dense.generate(np.stack([src, src]),
                                 np.stack([prompt, prompt]),
                                 np.array([P, P], np.int64), 8)
        paged = build_paged_decode_session(
            model, B, S, P, C, end_id=1, page_tokens=4, pool_pages=8,
            prefix_cache_size=2)
        hit0 = monitor.counter("decode_prefix_hit_total").value
        miss0 = monitor.counter("decode_prefix_miss_total").value
        shared0 = monitor.counter("decode_pages_shared_total").value
        m0 = _cm()
        slot_a, ra = paged.join(src, prompt, max_new_tokens=8)
        slot_b, rb = paged.join(src, prompt, max_new_tokens=8)
        m1 = _cm()
        assert ra is None and rb is None
        assert monitor.counter("decode_prefix_miss_total").value \
            - miss0 == 1
        assert monitor.counter("decode_prefix_hit_total").value \
            - hit0 == 1
        # the hit costs zero compiles and zero prefill dispatches: only
        # the miss's batch-1 prefill compiled
        assert m1 - m0 == 1
        # cache insert + hit alias both bump the share counter
        assert monitor.counter("decode_pages_shared_total").value \
            > shared0
        done = _drain(paged, {})
    for slot in (slot_a, slot_b):
        toks = done[slot][0]
        assert np.array_equal(toks, np.asarray(base[0])[:toks.size]), (
            "slot %d: post-hit tokens diverged from dense" % slot)


# -- admission control ------------------------------------------------------
def test_pool_exhaustion_sheds_typed_overloaded():
    """A pool that cannot seat the prompt must raise ``Overloaded``
    (the serving tier's typed shed signal) at join, BEFORE the prefill
    dispatch, without leaking pages — and admit again once pages
    retire."""
    B, S, P, C = 4, 6, 8, 16
    rng = np.random.RandomState(3)
    src = rng.randint(2, 512, (B, S)).astype(np.int64)
    prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        # 2 pages per 8-token prompt at page_tokens=4; 4 usable pages
        # (page 0 is scratch) -> the pool seats TWO prompts while four
        # batch slots sit vacant: pages exhaust first
        paged = build_paged_decode_session(model, B, S, P, C, end_id=1,
                                           page_tokens=4, pool_pages=5)
        for b in range(2):
            _, ready = paged.join(src[b], prompt[b], max_new_tokens=2)
            assert ready is None
        assert paged.pool.free_pages == 0
        steps0 = monitor.counter("decode_steps_total").value
        with pytest.raises(Overloaded):
            paged.join(src[2], prompt[2], max_new_tokens=2)
        # the rejected join ran nothing and allocated nothing
        assert monitor.counter("decode_steps_total").value == steps0
        assert paged.pool.free_pages == 0
        assert paged.pool.live_pages == 4
        done = _drain(paged, {})
        assert len(done) == 2
        # pages are back -> the same request is admitted now
        slot, ready = paged.join(src[2], prompt[2], max_new_tokens=2)
        if ready is None:
            _drain(paged, {})
    assert paged.pool.live_pages == 0


# -- speculative decoding ---------------------------------------------------
def test_speculative_identity_compiles_and_acceptance_ceiling():
    """One dense baseline, two draft configurations: a shallow draft
    must emit bit-identical tokens for exactly two extra compiles and
    never retrace on reuse; a full-depth draft (draft == target) must
    hit the acceptance ceiling — every round accepts all k tokens."""
    B, S, P, C = 2, 6, 4, 32
    rng = np.random.RandomState(4)
    src = rng.randint(2, 512, (B, S)).astype(np.int64)
    prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
    plens = np.array([4, 3], np.int64)
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        dense = build_decode_session(model, B, S, P, C, end_id=1)
        base, base_fin = dense.generate(src, prompt, plens, 8)
        with pytest.raises(ValueError, match="k"):
            build_speculative_session(model, dense, k=1)
        m0 = _cm()
        spec = build_speculative_session(model, dense, k=3,
                                         draft_layers=1)
        toks, fin = spec.generate(src, prompt, plens, 8)
        m1 = _cm()
        toks2, _ = spec.generate(src, prompt, plens, 8)
        m2 = _cm()
        hist = monitor.get_metric("decode_spec_accepted_tokens")
        c0, s0 = hist.count, hist.sum
        full = build_speculative_session(
            model, dense, k=4, draft_layers=len(model.dec_layers))
        ftoks, _ = full.generate(src, prompt, plens, 8)
    assert m1 - m0 == 2, (
        "speculative tier cost %d compiles, want 2 (draft + verify)"
        % (m1 - m0))
    assert m2 == m1, "speculative generate retraced on reuse"
    assert np.array_equal(toks, base), (
        "speculative tokens diverged from plain greedy decode")
    assert np.array_equal(toks2, base)
    assert np.array_equal(fin, base_fin)
    assert np.array_equal(ftoks, base)
    accepted = (hist.sum - s0) / max(1, hist.count - c0)
    assert accepted == 4.0, (
        "full-depth draft accepted %.2f tokens/step, want the ceiling "
        "k=4" % accepted)


# -- Pallas paged kernel ----------------------------------------------------
def test_paged_kernel_matches_gather_oracle_at_odd_page_counts(
        monkeypatch):
    """Take the Pallas paged tier (interpret mode on CPU, the capacity
    threshold patched low) at odd and prime pages-per-stream and check it
    against gather+dense-reference — the exact fallback the sessions use
    below the kernel threshold."""
    from paddle_tpu.kernels import attention as A

    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 3 * 8)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    B, H, d, ptok = 2, 2, 8, 8
    rng = np.random.RandomState(6)
    for npages in (3, 7, 13):
        C = npages * ptok
        P = B * npages + 1
        k_pool = rng.randn(P, H, ptok, d).astype(np.float32)
        v_pool = rng.randn(P, H, ptok, d).astype(np.float32)
        q = rng.randn(B, H, 1, d).astype(np.float32)
        pages = rng.permutation(np.arange(1, P))[:B * npages]
        table = pages.reshape(B, npages).astype(np.int32)
        lens = np.array([C - 3, (C // 2) + 1], np.int32)
        c0 = monitor.counter("attn_kernel_dispatch_total",
                             labels={"tier": "paged"}).value
        got = np.asarray(A.paged_attention_cache(
            q, k_pool, v_pool, table, lens))
        c1 = monitor.counter("attn_kernel_dispatch_total",
                             labels={"tier": "paged"}).value
        assert c1 > c0, "paged tier fell back (npages=%d)" % npages
        want = np.asarray(A._ref_attention_cache(
            q, A.gather_paged_cache(k_pool, table),
            A.gather_paged_cache(v_pool, table), lens,
            1.0 / math.sqrt(d)))
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6,
                                   err_msg="npages=%d" % npages)


# -- continuous-batching scatter fusion ------------------------------------
def test_dense_stream_join_is_one_scatter_dispatch():
    """The mid-stream join scatters all 4L per-layer caches in ONE
    fused jitted dispatch — the counter is the regression guard against
    sliding back to 4L separate device calls per join."""
    B, S, P, C = 2, 6, 4, 24
    rng = np.random.RandomState(7)
    src = rng.randint(2, 512, (B, S)).astype(np.int64)
    prompt = rng.randint(2, 512, (B, P)).astype(np.int64)
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        sess = build_decode_session(model, B, S, P, C, end_id=1,
                                    slot_prefill=True)
    st = sess.open_stream()
    c0 = monitor.counter("decode_slot_scatter_dispatch_total").value
    for b in range(B):
        st.join(src[b], prompt[b], max_new_tokens=3)
    c1 = monitor.counter("decode_slot_scatter_dispatch_total").value
    assert c1 - c0 == B, (
        "%d joins dispatched %d cache scatters, want one fused scatter "
        "per join" % (B, c1 - c0))
    while st.active_count:
        st.step()


# -- predictor routing ------------------------------------------------------
def test_generative_predictor_paged_stream_recompiles_flat():
    from paddle_tpu import inference
    from paddle_tpu.models.transformer import PagedDecodeSession

    rng = np.random.RandomState(8)
    src = rng.randint(2, 512, (2, 6)).astype(np.int64)
    prompt = rng.randint(2, 512, (2, 4)).astype(np.int64)
    p = inference.GenerativePredictor(
        Transformer.tiny(), batch_size=2, src_len=6, prompt_len=4,
        cache_capacity=16, end_id=1, paged=True, page_tokens=4,
        prefix_cache_size=2)
    st = p.open_stream()
    assert isinstance(st, PagedDecodeSession)
    with pytest.raises(ValueError, match="open_stream"):
        p.run({"src": src, "prompt": prompt}, max_new_tokens=2)
    rec0 = monitor.counter("predictor_shape_recompile_total").value
    done = {}
    for b in range(2):
        slot, ready = st.join(src[b], prompt[b], max_new_tokens=4)
        if ready is not None:
            done[slot] = ready
    _drain(st, done)
    assert len(done) == 2
    assert monitor.counter("predictor_shape_recompile_total").value \
        == rec0, "paged stream bumped the predictor recompile counter"


# -- geometry validation ----------------------------------------------------
def test_paged_session_validates_geometry():
    with fluid.dygraph.guard():
        model = Transformer.tiny()
        with pytest.raises(ValueError, match="page_tokens"):
            build_paged_decode_session(model, 2, 6, 4, 16, end_id=1,
                                       page_tokens=5)
        with pytest.raises(ValueError, match="pool_pages"):
            build_paged_decode_session(model, 2, 6, 4, 16, end_id=1,
                                       page_tokens=4, pool_pages=3)
