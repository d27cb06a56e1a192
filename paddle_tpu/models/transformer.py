"""Transformer NMT model in DyGraph (eager) mode — BASELINE.json
`configs` entry 5 (dygraph tracer -> XLA JIT).

Parity: reference ``tests/unittests/dist_transformer.py`` (the
Transformer-big NMT workload) and the dygraph transformer tests
(``test_dygraph_transformer`` family), rebuilt on the eager tracer. The
eager path executes each traced op via the same XLA lowering as the static
path with a per-op compile cache; `dygraph.jit.trace` then records the whole
forward into one static Program that jit-compiles into a single fused XLA
program — the TPU-native counterpart of the reference's
``imperative/jit/program_desc_tracer``.
"""

import collections
import hashlib
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, monitor
from paddle_tpu.fluid.dygraph import Layer, nn
from paddle_tpu.fluid.resilience import Overloaded


def _t():
    return framework._dygraph_tracer()


def _op(type, inputs, outs, attrs=None):
    return _t().trace_op(type, inputs, outs, attrs or {})


# -- functional eager helpers (tracer-backed) --------------------------------
def reshape(x, shape):
    (out,) = _op("reshape", {"X": [x]}, ["Out"], {"shape": list(shape)})
    return out


def transpose(x, perm):
    (out,) = _op("transpose", {"X": [x]}, ["Out"], {"axis": list(perm)})
    return out


def matmul(x, y, transpose_y=False, alpha=1.0):
    (out,) = _op("matmul", {"X": [x], "Y": [y]}, ["Out"],
                 {"transpose_X": False, "transpose_Y": transpose_y,
                  "alpha": alpha})
    return out


def softmax(x):
    (out,) = _op("softmax", {"X": [x]}, ["Out"], {"axis": -1})
    return out


def dropout(x, p, is_test=False):
    if is_test or not p:
        return x
    (out,) = _op("dropout", {"X": [x]}, ["Out"],
                 {"dropout_prob": p,
                  "dropout_implementation": "upscale_in_train"})
    return out


def softmax_ce(logits, label):
    outs = _op("softmax_with_cross_entropy", {"Logits": [logits],
                                              "Label": [label]},
               ["Softmax", "Loss"], {"soft_label": False})
    return outs[1]


def reduce_sum(x, dim=None, keep_dim=False):
    (out,) = _op("reduce_sum", {"X": [x]}, ["Out"],
                 {"dim": [] if dim is None else [dim],
                  "keep_dim": keep_dim, "reduce_all": dim is None})
    return out


def _tp_attrs(model_axis, kind):
    """(param_attr, bias_attr) for a Megatron-sharded Linear: 'col'
    splits the OUTPUT features over the model axis (bias rides along),
    'row' splits the INPUT features (bias stays replicated — it adds
    AFTER the partial products are reduced). None model_axis = dense."""
    if model_axis is None:
        return None, None
    from paddle_tpu.fluid.param_attr import ParamAttr

    if kind == "col":
        return (ParamAttr(shard=(None, model_axis)),
                ParamAttr(shard=(model_axis,)))
    return ParamAttr(shard=(model_axis, None)), None


class MultiHeadAttention(Layer):
    def __init__(self, d_model, n_heads, dropout_rate=0.1,
                 model_axis=None):
        super().__init__()
        self.n_heads = n_heads
        self.d_key = d_model // n_heads
        self.dropout_rate = dropout_rate
        # Megatron split: QKV column-parallel (each shard owns H/size
        # whole heads), output row-parallel (one psum per attention
        # block, inserted by the compiler from these shard specs)
        cw, cb = _tp_attrs(model_axis, "col")
        rw, rb = _tp_attrs(model_axis, "row")
        self.q_fc = nn.Linear(d_model, d_model, param_attr=cw, bias_attr=cb)
        self.k_fc = nn.Linear(d_model, d_model, param_attr=cw, bias_attr=cb)
        self.v_fc = nn.Linear(d_model, d_model, param_attr=cw, bias_attr=cb)
        self.out_fc = nn.Linear(d_model, d_model, param_attr=rw,
                                bias_attr=rb)

    def _split(self, t):
        t = reshape(t, [t.shape[0], -1, self.n_heads, self.d_key])
        return transpose(t, [0, 2, 1, 3])

    def _q_head(self, q):
        return self._split(self.q_fc(q))

    def _kv_heads(self, kv):
        """Projected split-head K/V [B, H, S, d] — ALSO the tensors the
        decode path writes into the KV ring caches (prefill) or
        precomputes once for cross-attention."""
        return self._split(self.k_fc(kv)), self._split(self.v_fc(kv))

    def _attend(self, qh, kh, vh, bias):
        scores = matmul(qh, kh, transpose_y=True,
                        alpha=1.0 / math.sqrt(self.d_key))
        if bias is not None:
            scores = scores + bias
        w = dropout(softmax(scores), self.dropout_rate,
                    is_test=not self.training)
        return self._merge_out(matmul(w, vh))

    def _merge_out(self, ctx):
        ctx = transpose(ctx, [0, 2, 1, 3])
        ctx = reshape(ctx, [ctx.shape[0], -1, self.n_heads * self.d_key])
        return self.out_fc(ctx)

    def forward(self, q, kv, bias):
        qh = self._q_head(q)
        kh, vh = self._kv_heads(kv)
        return self._attend(qh, kh, vh, bias)

    def forward_seq(self, q, kv, bias, causal, strategy="auto"):
        """Sequence-parallel self-attention: the fc outputs go into the
        ``sequence_parallel_attention`` op STILL PACKED [B, S, H*d] (the
        block-parallel layout — head split/merge happens inside the
        shard, so the graph carries no [B, H, S, d] transposes and every
        surrounding op keeps the clean [B, S, D] layout the 'sp' axis
        shards). ``bias`` is the optional k-side padding mask
        [B, 1, 1, S]; the causal triangle comes from ``causal``, not
        from a materialized [S, S] bias feed."""
        inputs = {"Q": [self.q_fc(q)], "K": [self.k_fc(kv)],
                  "V": [self.v_fc(kv)]}
        if bias is not None:
            inputs["Bias"] = [bias]
        (out,) = _op("sequence_parallel_attention", inputs, ["Out"],
                     {"n_heads": self.n_heads, "causal": bool(causal),
                      "dropout_prob": self.dropout_rate,
                      "is_test": not self.training,
                      "scale": 1.0 / math.sqrt(self.d_key),
                      "strategy": strategy})
        return self.out_fc(out)

    def forward_cached(self, x, k_cache, v_cache, cache_len,
                       causal_window=False):
        """ONE decode step of self-attention: project the incoming
        token(s), write K/V into the ring caches at slot cache_len % C,
        then attend q against the cache with the post-update length (so
        the token sees itself). ``causal_window=True`` makes q row r of
        a T-token write see only positions < cache_len + r + 1 — the
        exact mask T successive single-token steps would have seen (the
        speculative verify path). Returns (out, k_cache', v_cache',
        cache_len + T)."""
        qh = self._q_head(x)
        kh, vh = self._kv_heads(x)
        k_new, new_len = _op("kv_cache_update",
                             {"Cache": [k_cache], "New": [kh],
                              "CacheLen": [cache_len]}, ["Out", "OutLen"])
        v_new, _ = _op("kv_cache_update",
                       {"Cache": [v_cache], "New": [vh],
                        "CacheLen": [cache_len]}, ["Out", "OutLen"])
        (ctx,) = _op("fused_multihead_attention_cache",
                     {"Q": [qh], "KCache": [k_new], "VCache": [v_new],
                      "CacheLen": [new_len]}, ["Out"],
                     {"scale": 1.0 / math.sqrt(self.d_key),
                      "causal_window": bool(causal_window)})
        return self._merge_out(ctx), k_new, v_new, new_len

    def forward_paged(self, x, k_pool, v_pool, page_table, cache_len):
        """ONE decode step of self-attention against PAGED caches: the
        incoming token's K/V land in the shared block pool at whatever
        pool page the slot's table maps its write position to, and
        attention gathers context back through the same table. Same
        math as forward_cached — the (pool, table) pair is just a
        scattered layout of the per-slot ring."""
        qh = self._q_head(x)
        kh, vh = self._kv_heads(x)
        k_new, new_len = _op("paged_kv_cache_update",
                             {"Pool": [k_pool], "New": [kh],
                              "PageTable": [page_table],
                              "CacheLen": [cache_len]}, ["Out", "OutLen"])
        v_new, _ = _op("paged_kv_cache_update",
                       {"Pool": [v_pool], "New": [vh],
                        "PageTable": [page_table],
                        "CacheLen": [cache_len]}, ["Out", "OutLen"])
        (ctx,) = _op("paged_multihead_attention_cache",
                     {"Q": [qh], "KPool": [k_new], "VPool": [v_new],
                      "PageTable": [page_table], "CacheLen": [new_len]},
                     ["Out"], {"scale": 1.0 / math.sqrt(self.d_key)})
        return self._merge_out(ctx), k_new, v_new, new_len


class FFN(Layer):
    def __init__(self, d_model, d_inner, dropout_rate=0.1,
                 model_axis=None):
        super().__init__()
        cw, cb = _tp_attrs(model_axis, "col")
        rw, rb = _tp_attrs(model_axis, "row")
        self.fc1 = nn.Linear(d_model, d_inner, act="relu",
                             param_attr=cw, bias_attr=cb)
        self.fc2 = nn.Linear(d_inner, d_model, param_attr=rw,
                             bias_attr=rb)
        self.dropout_rate = dropout_rate

    def forward(self, x):
        return self.fc2(dropout(self.fc1(x), self.dropout_rate,
                                is_test=not self.training))


class EncoderLayer(Layer):
    def __init__(self, d_model, n_heads, d_inner, dropout_rate=0.1,
                 model_axis=None):
        super().__init__()
        self.attn = MultiHeadAttention(d_model, n_heads, dropout_rate,
                                       model_axis=model_axis)
        self.ffn = FFN(d_model, d_inner, dropout_rate,
                       model_axis=model_axis)
        self.ln1 = nn.LayerNorm(normalized_shape=[d_model], begin_norm_axis=2)
        self.ln2 = nn.LayerNorm(normalized_shape=[d_model], begin_norm_axis=2)
        self.dropout_rate = dropout_rate
        self.seq_parallel = False
        self.attn_strategy = "auto"

    def forward(self, x, bias):
        if self.seq_parallel:
            # src_bias is already the [B, 1, 1, S] k-side form the sp op
            # takes; encoder self-attention is non-causal
            y = self.attn.forward_seq(x, x, bias, causal=False,
                                      strategy=self.attn_strategy)
        else:
            y = self.attn(x, x, bias)
        x = self.ln1(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.ffn(x)
        return self.ln2(x + dropout(y, self.dropout_rate,
                                    is_test=not self.training))


class DecoderLayer(Layer):
    def __init__(self, d_model, n_heads, d_inner, dropout_rate=0.1,
                 model_axis=None):
        super().__init__()
        self.self_attn = MultiHeadAttention(d_model, n_heads, dropout_rate,
                                            model_axis=model_axis)
        self.cross_attn = MultiHeadAttention(d_model, n_heads, dropout_rate,
                                             model_axis=model_axis)
        self.ffn = FFN(d_model, d_inner, dropout_rate,
                       model_axis=model_axis)
        self.ln1 = nn.LayerNorm(normalized_shape=[d_model], begin_norm_axis=2)
        self.ln2 = nn.LayerNorm(normalized_shape=[d_model], begin_norm_axis=2)
        self.ln3 = nn.LayerNorm(normalized_shape=[d_model], begin_norm_axis=2)
        self.dropout_rate = dropout_rate
        self.seq_parallel = False
        self.attn_strategy = "auto"

    def forward(self, x, enc, self_bias, cross_bias):
        if self.seq_parallel:
            # the causal triangle comes from the kernel's causal=True,
            # not a materialized [S, S] bias feed — the dense triangle
            # would have to be replicated to every shard, defeating the
            # point of sequence sharding. Cross-attention stays on the
            # regular path: its q-length != kv-length rectangle is
            # GSPMD's job, not the equal-chunk ring's.
            y = self.self_attn.forward_seq(x, x, None, causal=True,
                                           strategy=self.attn_strategy)
        else:
            y = self.self_attn(x, x, self_bias)
        x = self.ln1(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.cross_attn(x, enc, cross_bias)
        x = self.ln2(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.ffn(x)
        return self.ln3(x + dropout(y, self.dropout_rate,
                                    is_test=not self.training))

    def forward_prefill(self, x, enc, self_bias, cross_bias, k_cache,
                        v_cache, cache_len):
        """Prompt pass: the exact math of forward() — same ops, same
        causal bias — while ALSO writing this layer's prompt K/V into
        the ring caches (cache_len = 0, so slots 0..T-1)."""
        qh = self.self_attn._q_head(x)
        kh, vh = self.self_attn._kv_heads(x)
        k_new, _ = _op("kv_cache_update",
                       {"Cache": [k_cache], "New": [kh],
                        "CacheLen": [cache_len]}, ["Out", "OutLen"])
        v_new, _ = _op("kv_cache_update",
                       {"Cache": [v_cache], "New": [vh],
                        "CacheLen": [cache_len]}, ["Out", "OutLen"])
        y = self.self_attn._attend(qh, kh, vh, self_bias)
        x = self.ln1(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.cross_attn(x, enc, cross_bias)
        x = self.ln2(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.ffn(x)
        return self.ln3(x + dropout(y, self.dropout_rate,
                                    is_test=not self.training)), k_new, v_new

    def forward_step(self, x, cross_k, cross_v, k_cache, v_cache,
                     cache_len, cross_bias, causal_window=False):
        """ONE decode step: cached self-attention (q_len=1 vs the KV
        ring buffer) and cross-attention against the PRECOMPUTED
        encoder K/V — no re-projection of the encoder output.
        ``causal_window`` is the multi-token (speculative verify)
        per-row mask of MultiHeadAttention.forward_cached."""
        y, k_new, v_new, new_len = self.self_attn.forward_cached(
            x, k_cache, v_cache, cache_len, causal_window=causal_window)
        x = self.ln1(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.cross_attn._attend(self.cross_attn._q_head(x), cross_k,
                                    cross_v, cross_bias)
        x = self.ln2(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.ffn(x)
        return self.ln3(x + dropout(y, self.dropout_rate,
                                    is_test=not self.training)), \
            k_new, v_new, new_len

    def forward_step_paged(self, x, cross_k, cross_v, k_pool, v_pool,
                           page_table, cache_len, cross_bias):
        """forward_step with the self-attention KV state in the shared
        page pool instead of a per-slot dense ring."""
        y, k_new, v_new, new_len = self.self_attn.forward_paged(
            x, k_pool, v_pool, page_table, cache_len)
        x = self.ln1(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.cross_attn._attend(self.cross_attn._q_head(x), cross_k,
                                    cross_v, cross_bias)
        x = self.ln2(x + dropout(y, self.dropout_rate,
                                 is_test=not self.training))
        y = self.ffn(x)
        return self.ln3(x + dropout(y, self.dropout_rate,
                                    is_test=not self.training)), \
            k_new, v_new, new_len


class Transformer(Layer):
    """Encoder-decoder transformer for teacher-forced NMT training."""

    def __init__(self, src_vocab, tgt_vocab, d_model=512, n_heads=8,
                 d_inner=2048, n_layers=6, max_len=256, dropout_rate=0.1,
                 seq_parallel=False, attn_strategy="auto",
                 model_axis=None):
        super().__init__()
        self.d_model = d_model
        self.n_heads = n_heads
        self.max_len = max_len
        self.model_axis = model_axis
        # embeddings and the output projection stay replicated under TP:
        # sharding them over 'model' would make the softmax+CE vocab-
        # parallel, a different (all-gather-bearing) lowering
        self.src_emb = nn.Embedding(size=[src_vocab, d_model])
        self.tgt_emb = nn.Embedding(size=[tgt_vocab, d_model])
        self.pos_emb = nn.Embedding(size=[max_len, d_model])
        self.enc_layers = [EncoderLayer(d_model, n_heads, d_inner,
                                        dropout_rate,
                                        model_axis=model_axis)
                           for _ in range(n_layers)]
        self.dec_layers = [DecoderLayer(d_model, n_heads, d_inner,
                                        dropout_rate,
                                        model_axis=model_axis)
                           for _ in range(n_layers)]
        for i, l in enumerate(self.enc_layers):
            self.add_sublayer("enc_%d" % i, l)
        for i, l in enumerate(self.dec_layers):
            self.add_sublayer("dec_%d" % i, l)
        self.proj = nn.Linear(d_model, tgt_vocab)
        self.dropout_rate = dropout_rate
        self.last_checkpoints = []
        self.set_seq_parallel(seq_parallel, attn_strategy)

    def set_seq_parallel(self, enabled, strategy="auto"):
        """Route every encoder/decoder SELF-attention through the
        sequence-parallel op (ring or Ulysses over the 'sp' mesh axis).
        Toggleable post-construction so one model instance can trace
        both the single-device oracle and the sharded program."""
        self.seq_parallel = bool(enabled)
        self.attn_strategy = strategy
        for l in self.enc_layers + self.dec_layers:
            l.seq_parallel = bool(enabled)
            l.attn_strategy = strategy
        return self

    def checkpoint_vars(self, program):
        """The per-block checkpoint Variables of the LAST traced forward,
        resolved in ``program`` (the jit.trace output) — feed these to
        ``RecomputeOptimizer._set_checkpoints`` so each attention+FFN
        block's activations are rematerialized in the backward pass
        instead of held live across it (the long-context memory knob)."""
        blk = program.global_block()
        return [blk.var(n) for n in self.last_checkpoints]

    @staticmethod
    def big(src_vocab=32000, tgt_vocab=32000):
        return Transformer(src_vocab, tgt_vocab, d_model=1024, n_heads=16,
                           d_inner=4096, n_layers=6)

    @staticmethod
    def tiny(src_vocab=512, tgt_vocab=512, **kw):
        return Transformer(src_vocab, tgt_vocab, d_model=32, n_heads=4,
                           d_inner=64, n_layers=2, max_len=64, **kw)

    def _embed(self, ids, emb, pos_ids):
        x = emb(ids)
        (x,) = _op("scale", {"X": [x]}, ["Out"],
                   {"scale": math.sqrt(self.d_model), "bias": 0.0,
                    "bias_after_scale": True})
        return x + self.pos_emb(pos_ids) if pos_ids is not None else x

    def forward(self, src_ids, tgt_ids, pos_src, pos_tgt, causal_bias,
                src_bias=None):
        """src_bias: optional [B, 1, 1, S_src] additive padding mask (0 keep,
        -1e4 pad) applied to encoder self-attention and decoder
        cross-attention; None = no source padding."""
        self.last_checkpoints = []
        enc = dropout(self._embed(src_ids, self.src_emb, pos_src),
                      self.dropout_rate, is_test=not self.training)
        for l in self.enc_layers:
            enc = l(enc, src_bias)
            self.last_checkpoints.append(enc.name)
        dec = dropout(self._embed(tgt_ids, self.tgt_emb, pos_tgt),
                      self.dropout_rate, is_test=not self.training)
        for l in self.dec_layers:
            dec = l(dec, enc, causal_bias, src_bias)
            self.last_checkpoints.append(dec.name)
        return self.proj(dec)

    # -- incremental decode (prefill + per-token step) -----------------------
    def prefill(self, src_ids, tgt_ids, pos_src, pos_tgt, causal_bias,
                cache_len, *rest):
        """Prefill phase: run the encoder and the prompt through the
        decoder stack ONCE, populating the per-layer KV ring caches and
        precomputing the per-layer cross-attention K/V of the encoder
        output. ``rest`` is L self-K caches, L self-V caches
        [B, H, C, d] (zeros, capacity C >= prompt length), then an
        optional src padding bias. Returns (prompt logits [B, P, V],
        L updated K caches, L updated V caches, L cross-K, L cross-V)."""
        L = len(self.dec_layers)
        k_caches, v_caches = rest[:L], rest[L:2 * L]
        src_bias = rest[2 * L] if len(rest) > 2 * L else None
        enc = dropout(self._embed(src_ids, self.src_emb, pos_src),
                      self.dropout_rate, is_test=not self.training)
        for l in self.enc_layers:
            enc = l(enc, src_bias)
        dec = dropout(self._embed(tgt_ids, self.tgt_emb, pos_tgt),
                      self.dropout_rate, is_test=not self.training)
        out_k, out_v, cross_k, cross_v = [], [], [], []
        for l, kc, vc in zip(self.dec_layers, k_caches, v_caches):
            ck, cv = l.cross_attn._kv_heads(enc)
            cross_k.append(ck)
            cross_v.append(cv)
            dec, k_new, v_new = l.forward_prefill(
                dec, enc, causal_bias, src_bias, kc, vc, cache_len)
            out_k.append(k_new)
            out_v.append(v_new)
        logits = self.proj(dec)
        return tuple([logits] + out_k + out_v + cross_k + cross_v)

    def decode_step(self, tok, finished, end_ids, cache_len, *rest):
        """ONE greedy decode step (q_len=1): embed the incoming token at
        its absolute position (= cache_len, derived on-device), run the
        decoder stack against the KV ring caches and precomputed cross
        K/V, project, argmax, and advance the finished mask. ``rest`` is
        L cross-K, L cross-V, L self-K caches, L self-V caches, then an
        optional src padding bias. Returns (next_tok [B, 1] int64,
        new_len [B] int32, finished' [B, 1] bool, L updated K caches,
        L updated V caches) — everything a subsequent identical step
        feeds back, so the step traces exactly once."""
        L = len(self.dec_layers)
        cross_k, cross_v = rest[:L], rest[L:2 * L]
        k_caches, v_caches = rest[2 * L:3 * L], rest[3 * L:4 * L]
        src_bias = rest[4 * L] if len(rest) > 4 * L else None
        B = tok.shape[0]
        # ids with a trailing dim of 1 are squeezed by lookup_table, so a
        # [B, 1] token would embed to [B, D]; [B, 1, 1] keeps the q_len=1
        # axis. The position is the pre-update cache length.
        pos = reshape(cache_len, [B, 1, 1])
        x = dropout(self._embed(reshape(tok, [B, 1, 1]), self.tgt_emb,
                                pos),
                    self.dropout_rate, is_test=not self.training)
        new_k, new_v, new_len = [], [], None
        for l, ck, cv, kc, vc in zip(self.dec_layers, cross_k, cross_v,
                                     k_caches, v_caches):
            x, k_new, v_new, new_len = l.forward_step(
                x, ck, cv, kc, vc, cache_len, src_bias)
            new_k.append(k_new)
            new_v.append(v_new)
        nxt, fin = self._next_token(self.proj(x), finished, end_ids)
        return tuple([nxt, new_len, fin] + new_k + new_v)

    def _next_token(self, logits, finished, end_ids):
        """Greedy argmax -> end_id forcing -> finished-mask advance (the
        shared tail of every decode-step variant)."""
        (nxt,) = _op("arg_max", {"X": [logits]}, ["Out"], {"axis": -1})
        (nxt,) = _op("where", {"Condition": [finished], "X": [end_ids],
                               "Y": [nxt]}, ["Out"])
        (is_end,) = _op("equal", {"X": [nxt], "Y": [end_ids]}, ["Out"])
        (fin,) = _op("logical_or", {"X": [finished], "Y": [is_end]},
                     ["Out"])
        return nxt, fin

    def decode_step_paged(self, tok, finished, end_ids, cache_len,
                          page_table, *rest):
        """decode_step with the per-layer self-attention KV state in a
        SHARED page pool: ``page_table`` [B, n_pages] int32 maps each
        slot's logical ring pages to pool rows (row 0 = the scratch
        page every idle/unallocated entry points at, so the program
        writes unconditionally and stays shape-closed). ``rest`` is
        L cross-K, L cross-V, then L K pools and L V pools
        [P, H, page_tokens, d], then an optional src padding bias.
        Returns (next_tok, new_len, finished', L K pools, L V pools) —
        the dense ring's contract with pools in place of caches."""
        L = len(self.dec_layers)
        cross_k, cross_v = rest[:L], rest[L:2 * L]
        k_pools, v_pools = rest[2 * L:3 * L], rest[3 * L:4 * L]
        src_bias = rest[4 * L] if len(rest) > 4 * L else None
        B = tok.shape[0]
        pos = reshape(cache_len, [B, 1, 1])
        x = dropout(self._embed(reshape(tok, [B, 1, 1]), self.tgt_emb,
                                pos),
                    self.dropout_rate, is_test=not self.training)
        new_k, new_v, new_len = [], [], None
        for l, ck, cv, kp, vp in zip(self.dec_layers, cross_k, cross_v,
                                     k_pools, v_pools):
            x, k_new, v_new, new_len = l.forward_step_paged(
                x, ck, cv, kp, vp, page_table, cache_len, src_bias)
            new_k.append(k_new)
            new_v.append(v_new)
        nxt, fin = self._next_token(self.proj(x), finished, end_ids)
        return tuple([nxt, new_len, fin] + new_k + new_v)

    def decode_step_draft(self, tok, finished, end_ids, cache_len,
                          *rest):
        """decode_step through only the FIRST len(rest)//4 decoder
        layers — the self-speculative DRAFT: same embeddings, same
        output projection, truncated depth, its own (shallow) KV
        caches. ``rest`` is Ld cross-K, Ld cross-V, Ld K caches, Ld V
        caches. Draft quality only affects how many proposals the
        verify step accepts, never which tokens are emitted."""
        Ld = len(rest) // 4
        cross_k, cross_v = rest[:Ld], rest[Ld:2 * Ld]
        k_caches, v_caches = rest[2 * Ld:3 * Ld], rest[3 * Ld:4 * Ld]
        B = tok.shape[0]
        pos = reshape(cache_len, [B, 1, 1])
        x = dropout(self._embed(reshape(tok, [B, 1, 1]), self.tgt_emb,
                                pos),
                    self.dropout_rate, is_test=not self.training)
        new_k, new_v, new_len = [], [], None
        for l, ck, cv, kc, vc in zip(self.dec_layers[:Ld], cross_k,
                                     cross_v, k_caches, v_caches):
            x, k_new, v_new, new_len = l.forward_step(
                x, ck, cv, kc, vc, cache_len, None)
            new_k.append(k_new)
            new_v.append(v_new)
        nxt, fin = self._next_token(self.proj(x), finished, end_ids)
        return tuple([nxt, new_len, fin] + new_k + new_v)

    def verify_step(self, toks, step_ids, cache_len, *rest):
        """Speculative VERIFY: consume k proposed tokens in ONE
        dispatch. ``toks`` [B, k] int32 are the draft's proposals
        d_0..d_{k-1} (d_0 is the round's pending, already-emitted
        token); they are written into the ring caches and attended with
        the per-row causal window — q row r sees positions
        < cache_len + r + 1, exactly what r+1 single-token steps would
        have seen. ``step_ids`` [1, k] int32 = arange(k), fed (not
        baked in) so position arithmetic stays inside the shape-closed
        program. ``rest`` is L cross-K, L cross-V, L K caches, L V
        caches. Returns (greedy [B, k], new_len [B], L K caches, L V
        caches): greedy[:, i] is the target's next token after
        consuming toks[:, :i+1]; the host accepts the longest prefix
        with toks[:, i] == greedy[:, i-1] and rolls cache_len back to
        cache_len + accepted (stale cache rows above the new length are
        masked until overwritten — callers must keep the window inside
        the ring, i.e. no wraparound)."""
        L = len(self.dec_layers)
        cross_k, cross_v = rest[:L], rest[L:2 * L]
        k_caches, v_caches = rest[2 * L:3 * L], rest[3 * L:4 * L]
        B, K = toks.shape[0], toks.shape[1]
        pos = reshape(cache_len, [B, 1, 1]) + reshape(step_ids, [1, K, 1])
        x = dropout(self._embed(reshape(toks, [B, K, 1]), self.tgt_emb,
                                pos),
                    self.dropout_rate, is_test=not self.training)
        new_k, new_v, new_len = [], [], None
        for l, ck, cv, kc, vc in zip(self.dec_layers, cross_k, cross_v,
                                     k_caches, v_caches):
            x, k_new, v_new, new_len = l.forward_step(
                x, ck, cv, kc, vc, cache_len, None, causal_window=True)
            new_k.append(k_new)
            new_v.append(v_new)
        (greedy,) = _op("arg_max", {"X": [self.proj(x)]}, ["Out"],
                        {"axis": -1})
        return tuple([greedy, new_len] + new_k + new_v)


class EncoderTower(Layer):
    """Encoder-only LM tower (embed -> N encoder layers -> vocab proj).

    The pipeline-parallel workhorse: every encoder layer boundary
    carries the SAME [B, S, D] activation, so the tower admits uniform
    GPipe cuts at ANY stage count dividing the layer count — unlike the
    encoder-decoder Transformer, whose decoder-side cuts would need the
    encoder output bundled into every boundary. ``last_checkpoints``
    (layer-output var names, recorded per trace) are the cut
    candidates."""

    def __init__(self, vocab, d_model=64, n_heads=4, d_inner=128,
                 n_layers=4, max_len=64, dropout_rate=0.0,
                 model_axis=None):
        super().__init__()
        self.d_model = d_model
        self.emb = nn.Embedding(size=[vocab, d_model])
        self.pos_emb = nn.Embedding(size=[max_len, d_model])
        self.layers_ = [EncoderLayer(d_model, n_heads, d_inner,
                                     dropout_rate, model_axis=model_axis)
                        for _ in range(n_layers)]
        for i, l in enumerate(self.layers_):
            self.add_sublayer("tower_%d" % i, l)
        self.proj = nn.Linear(d_model, vocab)
        self.dropout_rate = dropout_rate
        self.last_checkpoints = []

    def forward(self, ids, pos):
        self.last_checkpoints = []
        x = self.emb(ids)
        (x,) = _op("scale", {"X": [x]}, ["Out"],
                   {"scale": math.sqrt(self.d_model), "bias": 0.0,
                    "bias_after_scale": True})
        x = dropout(x + self.pos_emb(pos), self.dropout_rate,
                    is_test=not self.training)
        for l in self.layers_:
            x = l(x, None)
            self.last_checkpoints.append(x.name)
        return self.proj(x)


def make_causal_bias(seq_len):
    m = np.triu(np.full((seq_len, seq_len), -1e4, np.float32), k=1)
    return m.reshape(1, 1, seq_len, seq_len)


def loss_fn(logits, labels):
    """Mean token cross-entropy. labels: [B, S, 1] int64."""
    ce = softmax_ce(logits, labels)
    total = reduce_sum(ce)
    n = float(np.prod(labels.shape))
    (loss,) = _op("scale", {"X": [total]}, ["Out"],
                  {"scale": 1.0 / n, "bias": 0.0, "bias_after_scale": True})
    return loss


def synthetic_batch(src_vocab, tgt_vocab, batch, seq_len, seed=0):
    rng = np.random.RandomState(seed)
    src = rng.randint(1, src_vocab, (batch, seq_len)).astype("int64")
    tgt = rng.randint(1, tgt_vocab, (batch, seq_len)).astype("int64")
    labels = rng.randint(1, tgt_vocab, (batch, seq_len, 1)).astype("int64")
    pos = np.tile(np.arange(seq_len, dtype="int64"), (batch, 1))
    return src, tgt, labels, pos


# ---------------------------------------------------------------------------
# Incremental decode sessions: trace (prefill, decode) once, reuse per token.
# ---------------------------------------------------------------------------

_M_DECODE_STEPS = monitor.counter(
    "decode_steps_total", "decode-program steps dispatched")
_M_DECODE_SECONDS = monitor.histogram(
    "decode_step_seconds", "per-token decode dispatch latency (async: "
    "excludes device sync, which happens once per generation)")
_M_DECODE_CACHE = monitor.gauge(
    "decode_cache_tokens", "live KV-cache tokens across the batch after "
    "the last generation (sum of min(len, capacity))")
_M_SLOT_JOIN = monitor.counter(
    "decode_slot_join_total", "requests prefilled into a vacant slot of "
    "a live continuous-batching decode stream")
_M_SLOT_RETIRE = monitor.counter(
    "decode_slot_retire_total", "continuous-batching slots retired "
    "(sequence finished or token budget reached)")
_M_SLOT_OCC = monitor.histogram(
    "decode_slot_occupancy", "active slots / batch width observed at "
    "each continuous-batching decode step (1.0 = full batch; drained "
    "batch-1 decoding sits at 1/width)",
    buckets=(0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
_M_SCATTER_DISPATCH = monitor.counter(
    "decode_slot_scatter_dispatch_total", "fused multi-cache slot "
    "scatters dispatched at continuous-batching join (ONE per join — "
    "the regression guard against the per-layer dispatch storm)")
_M_PAGES_ALLOC = monitor.counter(
    "decode_pages_allocated_total", "KV pages taken from the paged "
    "decode free list (prompt prefills, ring growth, copy-on-write "
    "splits)")
_M_PAGES_FREED = monitor.counter(
    "decode_pages_freed_total", "KV pages returned to the paged decode "
    "free list (refcount hit zero)")
_M_PAGES_SHARED = monitor.counter(
    "decode_pages_shared_total", "KV page aliasings: a joining slot's "
    "table pointed at already-resident prefix pages instead of "
    "re-prefilling them")
_M_PREFIX_HIT = monitor.counter(
    "decode_prefix_hit_total", "paged joins whose (src, prompt prefix) "
    "was served from the prefix cache — the prefill dispatch skipped "
    "entirely")
_M_PREFIX_MISS = monitor.counter(
    "decode_prefix_miss_total", "paged joins that had to prefill with "
    "prefix caching enabled (prefix not resident)")
_M_SPEC_ACCEPT = monitor.histogram(
    "decode_spec_accepted_tokens", "tokens emitted per speculative "
    "verify dispatch (1 = draft rejected at the first proposal, "
    "k = whole window accepted)",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16))


class _MethodShim(Layer):
    """Expose a bound model METHOD as a traceable Layer: jit.trace calls
    ``layer(*inputs)`` and walks ``layer.named_parameters()``, both of
    which resolve through the wrapped model."""

    def __init__(self, model, method):
        super().__init__()
        self.model = model          # __setattr__ registers the sublayer
        self._method = method

    def forward(self, *inputs):
        return getattr(self.model, self._method)(*inputs)


def run_cached_phases(exe, scope, phase1, feed1, fetch1, phase2, feed2,
                      fetch2, bridge, return_numpy=True):
    """Split-inference skeleton: run ``phase1`` ONCE, then run ``phase2``
    fed phase-1 fetches that never leave the device (return_numpy=False
    pass-through) — the expensive phase-1 computation is hoisted out of
    whatever loop drives phase 2. ``bridge`` maps phase-2 feed name ->
    phase-1 fetch index. Shared by the transformer prefill->decode pair
    and the seq2seq encoder->beam-decode split
    (models/seq2seq.py run_split_infer)."""
    outs = exe.run(phase1, feed=feed1, fetch_list=fetch1, scope=scope,
                   return_numpy=False)
    feed = dict(feed2 or {})
    for name, idx in bridge.items():
        feed[name] = outs[idx]
    return exe.run(phase2, feed=feed, fetch_list=fetch2, scope=scope,
                   return_numpy=return_numpy)


def build_decode_session(model, batch_size, src_len, prompt_len,
                         cache_capacity, end_id=1, use_compiled=True,
                         slot_prefill=False, seq_shards=1):
    """Trace ``model``'s (prefill, decode_step) pair at FIXED shapes and
    wrap them in a DecodeSession. Must run under fluid.dygraph.guard();
    puts the model in eval() mode (decode is inference-only — the
    traced programs carry no dropout ops).

    ``slot_prefill=True`` additionally traces the prefill at batch 1 —
    the program ``session.open_stream()`` uses to prefill ONE request's
    prompt into a vacant slot of a live decode batch (continuous
    batching) without touching the other slots. Three compiles total
    instead of two; the third is amortized over every mid-stream join.

    ``seq_shards=n`` (requires ``use_compiled``) lays the session over
    an n-device 'sp' mesh with the KV ring caches and precomputed cross
    K/V sharded on their sequence dim (dim 2 of [B, H, C, d]) — no
    device ever holds a full-capacity cache, so capacity scales with
    the mesh. Cache fetches stay pinned to the 'sp' layout, so the
    per-token feedback loop never all-gathers. ``cache_capacity`` and
    ``src_len`` must divide n."""
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.executor import Scope

    if cache_capacity < prompt_len:
        raise ValueError(
            "cache_capacity=%d < prompt_len=%d: the prefill write would "
            "cross the ring boundary" % (cache_capacity, prompt_len))
    seq_shards = int(seq_shards)
    if seq_shards > 1:
        if not use_compiled:
            raise ValueError("seq_shards > 1 needs use_compiled=True "
                             "(the sharding lives on CompiledProgram)")
        if cache_capacity % seq_shards or src_len % seq_shards:
            raise ValueError(
                "cache_capacity=%d and src_len=%d must both divide "
                "seq_shards=%d for the sequence dim to shard evenly"
                % (cache_capacity, src_len, seq_shards))
    model.eval()
    L = len(model.dec_layers)
    B, H = int(batch_size), model.n_heads
    d = model.d_model // model.n_heads
    C = int(cache_capacity)

    def zero_caches():
        return [np.zeros((B, H, C, d), np.float32) for _ in range(2 * L)]

    prefill_in = [
        np.zeros((B, src_len), np.int64),
        np.zeros((B, prompt_len), np.int64),
        np.tile(np.arange(src_len, dtype=np.int64), (B, 1)),
        np.tile(np.arange(prompt_len, dtype=np.int64), (B, 1)),
        make_causal_bias(prompt_len),
        np.zeros((B,), np.int32),
    ] + zero_caches()
    _, prefill_tl = dygraph.jit.trace(_MethodShim(model, "prefill"),
                                      prefill_in)

    # the decode boundary is int32-native: fetched tokens/lengths come
    # back as int32 jax.Arrays (x64 is disabled) and feed straight back
    # in, so the feed signature — and therefore the compile-cache key —
    # is identical from the first step to the last
    decode_in = [
        np.zeros((B, 1), np.int32),
        np.zeros((B, 1), bool),
        np.array([end_id], np.int32),
        np.full((B,), prompt_len, np.int32),
    ] + [np.zeros((B, H, src_len, d), np.float32)
         for _ in range(2 * L)] + zero_caches()
    _, decode_tl = dygraph.jit.trace(_MethodShim(model, "decode_step"),
                                     decode_in)

    prefill1_tl = None
    if slot_prefill:
        prefill1_in = [
            np.zeros((1, src_len), np.int64),
            np.zeros((1, prompt_len), np.int64),
            np.arange(src_len, dtype=np.int64).reshape(1, -1),
            np.arange(prompt_len, dtype=np.int64).reshape(1, -1),
            make_causal_bias(prompt_len),
            np.zeros((1,), np.int32),
        ] + [np.zeros((1, H, C, d), np.float32) for _ in range(2 * L)]
        _, prefill1_tl = dygraph.jit.trace(_MethodShim(model, "prefill"),
                                           prefill1_in)

    scope = Scope()
    for _, p in model.named_parameters():
        # The executor donates the state buffers to XLA on every run, so the
        # scope must own its copies — sharing ``p._ivar`` directly would
        # delete the eager model's parameter arrays on the first step.
        scope.set_var(p.name, jnp.array(p._ivar, copy=True))
    return DecodeSession(prefill_tl, decode_tl, scope, n_layers=L,
                         batch_size=B, src_len=src_len,
                         prompt_len=prompt_len, cache_capacity=C,
                         n_heads=H, d_key=d, end_id=end_id,
                         use_compiled=use_compiled, prefill1_tl=prefill1_tl,
                         seq_shards=seq_shards)


class DecodeSession:
    """Batched greedy autoregressive decoding over a traced (prefill,
    decode) program pair sharing one parameter scope.

    The decode program's feeds and fetches are shape-closed: every fetch
    (next token, per-sequence lengths, finished mask, updated ring
    caches) feeds straight back in as a ``jax.Array`` with an identical
    signature, so an N-token generation costs exactly TWO executor
    compiles (one prefill, one decode) and zero per-token host syncs —
    tokens materialize once, after the last step. Per-sequence lengths
    and the finished mask make batch slots independent: a finished slot
    keeps emitting end_id and can be re-prefixed by a later prefill
    (the continuous-batching hook for the serving tier)."""

    def __init__(self, prefill_tl, decode_tl, scope, n_layers, batch_size,
                 src_len, prompt_len, cache_capacity, n_heads, d_key,
                 end_id, use_compiled=True, prefill1_tl=None, seq_shards=1):
        self._exe = fluid.Executor()
        self.scope = scope
        self._L = n_layers
        self.batch_size = batch_size
        self.src_len = src_len
        self.prompt_len = prompt_len
        self.cache_capacity = cache_capacity
        self.end_id = int(end_id)
        self.n_heads = n_heads
        self.d_key = d_key
        self.seq_shards = int(seq_shards)
        self._use_compiled = bool(use_compiled)
        self._prefill_feeds = list(prefill_tl._feed_names)
        self._prefill_fetches = list(prefill_tl._fetch_names)
        self._decode_feeds = list(decode_tl._feed_names)
        self._decode_fetches = list(decode_tl._fetch_names)
        if use_compiled:
            self.prefill_program = fluid.CompiledProgram(prefill_tl.program)
            self.decode_program = fluid.CompiledProgram(decode_tl.program)
            if self.seq_shards > 1:
                L, n = n_layers, self.seq_shards
                # seq-dim positions: prefill feeds 6.. are the 2L zero
                # caches [B,H,C,d]; prefill fetches 1.. are 2L updated
                # caches + 2L cross K/V; decode feeds 4.. are 2L cross +
                # 2L caches; decode fetches 3.. are the 2L caches that
                # feed straight back. All shard dim 2 over 'sp'.
                self.prefill_program.with_data_parallel(
                    mesh_axes=("sp",), mesh_shape={"sp": n}, places=n,
                    seq_feeds={f: 2 for f in
                               self._prefill_feeds[6:6 + 2 * L]},
                    seq_fetches={f: 2 for f in
                                 self._prefill_fetches[1:1 + 4 * L]})
                self.decode_program.with_data_parallel(
                    mesh_axes=("sp",), mesh_shape={"sp": n}, places=n,
                    seq_feeds={f: 2 for f in
                               self._decode_feeds[4:4 + 4 * L]},
                    seq_fetches={f: 2 for f in
                                 self._decode_fetches[3:3 + 2 * L]})
        else:
            self.prefill_program = prefill_tl.program
            self.decode_program = decode_tl.program
        self.prefill1_program = None
        if prefill1_tl is not None:
            self._prefill1_feeds = list(prefill1_tl._feed_names)
            self._prefill1_fetches = list(prefill1_tl._fetch_names)
            self.prefill1_program = (
                fluid.CompiledProgram(prefill1_tl.program)
                if use_compiled else prefill1_tl.program)
        B, H, C, d = batch_size, n_heads, cache_capacity, d_key
        self._zero_caches = [np.zeros((B, H, C, d), np.float32)
                             for _ in range(2 * n_layers)]
        self._pos_src = np.tile(np.arange(src_len, dtype=np.int64), (B, 1))
        self._pos_tgt = np.tile(np.arange(prompt_len, dtype=np.int64),
                                (B, 1))
        self._causal = make_causal_bias(prompt_len)
        self._end_ids = np.array([self.end_id], np.int32)

    def generate(self, src, prompt, prompt_lens, max_new_tokens):
        """Greedy-decode ``max_new_tokens`` tokens per sequence.

        src [B, src_len] int64; prompt [B, prompt_len] int64 right-padded
        (first token is the GO symbol); prompt_lens [B] = true prompt
        lengths (pad slots are masked out of attention and overwritten
        by later decode writes). Returns (tokens [B, max_new_tokens]
        int64, finished [B] bool)."""
        B, L = self.batch_size, self._L
        src = np.ascontiguousarray(src, np.int64)
        prompt = np.ascontiguousarray(prompt, np.int64)
        plens = np.asarray(prompt_lens, np.int64).reshape(B)
        if src.shape != (B, self.src_len) or \
                prompt.shape != (B, self.prompt_len):
            raise ValueError(
                "shape mismatch: session traced for src %s / prompt %s, "
                "got %s / %s — pad or re-trace" %
                ((B, self.src_len), (B, self.prompt_len), src.shape,
                 prompt.shape))
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plens.min() < 1 or plens.max() > self.prompt_len:
            raise ValueError("prompt_lens must be in [1, %d]"
                             % self.prompt_len)

        feed = dict(zip(self._prefill_feeds,
                        [src, prompt, self._pos_src, self._pos_tgt,
                         self._causal, np.zeros((B,), np.int32)]
                        + self._zero_caches))
        outs = self._exe.run(self.prefill_program, feed=feed,
                             fetch_list=self._prefill_fetches,
                             scope=self.scope, return_numpy=False)
        logits = np.asarray(outs[0])                  # [B, P, V]
        kc, vc = outs[1:1 + L], outs[1 + L:1 + 2 * L]
        cross = outs[1 + 2 * L:1 + 4 * L]

        first = logits[np.arange(B), plens - 1, :].argmax(-1)
        tok = first.astype(np.int32)[:, None]
        finished = tok == self.end_id
        cache_len = plens.astype(np.int32)
        toks = [tok]
        for _ in range(max_new_tokens - 1):
            t0 = time.perf_counter()
            feed = dict(zip(self._decode_feeds,
                            [tok, finished, self._end_ids, cache_len]
                            + list(cross) + list(kc) + list(vc)))
            outs = self._exe.run(self.decode_program, feed=feed,
                                 fetch_list=self._decode_fetches,
                                 scope=self.scope, return_numpy=False)
            tok, cache_len, finished = outs[0], outs[1], outs[2]
            kc, vc = outs[3:3 + L], outs[3 + L:3 + 2 * L]
            toks.append(tok)
            _M_DECODE_STEPS.inc()
            _M_DECODE_SECONDS.observe(time.perf_counter() - t0)
        # host-side bookkeeping, no device sync: total tokens resident in
        # the ring after this generation
        _M_DECODE_CACHE.set(float(np.minimum(
            plens + max_new_tokens, self.cache_capacity).sum()))
        tokens = np.concatenate([np.asarray(t) for t in toks], axis=1)
        return tokens, np.asarray(finished).reshape(B)

    def open_stream(self):
        """A ``ContinuousDecodeSession`` over this session's programs:
        a live fixed-width decode batch where requests join vacant slots
        mid-stream (slot-level prefill) and finished slots retire
        without draining the batch. Requires the session to have been
        built with ``slot_prefill=True``."""
        if self.prefill1_program is None:
            raise ValueError(
                "continuous batching needs the batch-1 slot-prefill "
                "program: build_decode_session(..., slot_prefill=True)")
        return ContinuousDecodeSession(self)


class _SlotState:
    """Host-side bookkeeping for one active continuous-batching slot."""

    def __init__(self, tokens, budget):
        self.tokens = tokens        # emitted token ids (ints, grows)
        self.budget = int(budget)   # max_new_tokens for this request


@jax.jit
def _slot_scatter(state, updates, slot):
    """ONE fused device dispatch writing batch-1 rows into ``slot``
    across a whole list of batch-state arrays (ring caches, cross K/V).
    The unfused form was ~4L separate index-update dispatches per join,
    so admission latency scaled with model depth."""
    return [s.at[slot].set(u[0]) for s, u in zip(state, updates)]


@jax.jit
def _paged_pack(pools, caches, rows):
    """Scatter one prefilled request's [1, H, C, d] ring caches into
    its allocated pool pages — ONE dispatch across all 2L pools.
    ``rows`` [n_pages] int32 holds the slot's pool page per logical
    page; the unallocated tail points at the scratch page 0, whose
    writes are garbage by design (those logical pages sit past the
    prompt and are masked by cache_len until a real page replaces
    them)."""
    out = []
    for pool, c in zip(pools, caches):
        _, h, ptok, d = pool.shape
        src = jnp.transpose(jnp.reshape(c[0], (h, -1, ptok, d)),
                            (1, 0, 2, 3))
        out.append(pool.at[rows].set(src))
    return out


@jax.jit
def _paged_cow(pools, src_page, dst_page):
    """Copy one pool page across all 2L pools in one dispatch — the
    copy-on-write split when a slot is about to dirty a page it shares
    with the prefix cache (or another slot)."""
    return [p.at[dst_page].set(p[src_page]) for p in pools]


class ContinuousDecodeSession:
    """Slot-level continuous batching over a (prefill, slot-prefill,
    decode) program trio: the decode batch is a FIXED width of
    ``session.batch_size`` slots, each step runs the whole batch through
    the one compiled decode program, and between steps finished slots
    are retired while waiting requests' prompts are prefilled into the
    vacant slots (batch-1 prefill program, K/V scattered into the slot's
    rows of the live ring caches) — so decode-batch occupancy stays high
    under ragged generation lengths instead of draining to batch-1.

    Unlike ``DecodeSession.generate`` (zero per-token host syncs, one
    caller) this syncs the [B,1] token + finished fetches each step —
    the scheduler must see per-slot completion to retire/join. The big
    tensors (ring caches, cross K/V) never leave the device; joins and
    retires touch them only through on-device index updates. Slot rows
    are mathematically independent through the whole decode program (no
    cross-batch reductions), so a request's tokens are identical whether
    it shares the batch or runs alone — asserted in tests.

    Single-threaded by design: ``join``/``step`` dispatch through the
    session's executor. Serialize externally (inference.serving holds
    one dispatch lock) if multiple threads drive sessions."""

    def __init__(self, session):
        s = self._s = session
        B, H, C, d = (s.batch_size, s.n_heads, s.cache_capacity, s.d_key)
        L = s._L
        self._tok = np.full((B, 1), s.end_id, np.int32)
        self._fin = np.ones((B, 1), bool)
        # idle slots sit at cache_len=1 over zero caches: attention sees
        # one all-zero key (finite softmax), and the position embed stays
        # in range no matter how long the stream runs (re-clamped each
        # step in _clamp_idle)
        self._len = np.ones((B,), np.int32)
        self._kc = [np.zeros((B, H, C, d), np.float32) for _ in range(L)]
        self._vc = [np.zeros((B, H, C, d), np.float32) for _ in range(L)]
        self._cross = [np.zeros((B, H, s.src_len, d), np.float32)
                       for _ in range(2 * L)]
        self._slots = [None] * B    # _SlotState or None (vacant)
        self._zero_caches1 = [np.zeros((1, H, C, d), np.float32)
                              for _ in range(2 * L)]
        self._pos_src1 = np.arange(s.src_len, dtype=np.int64).reshape(1, -1)
        self._pos_tgt1 = np.arange(s.prompt_len,
                                   dtype=np.int64).reshape(1, -1)

    @property
    def width(self):
        return self._s.batch_size

    @property
    def active_count(self):
        return sum(st is not None for st in self._slots)

    def vacant_slots(self):
        return [i for i, st in enumerate(self._slots) if st is None]

    def _scatter(self, slot, outs):
        """Write one request's prefill results into ``slot``'s rows of
        the live batch state — ONE fused on-device index-update dispatch
        over every ring cache and cross K/V array (the caches never
        round-trip through the host, and join latency no longer scales
        with layer count)."""
        L = self._s._L
        state = [jnp.asarray(a)
                 for a in self._kc + self._vc + self._cross]
        updates = [jnp.asarray(u) for u in outs[1:1 + 4 * L]]
        new = _slot_scatter(state, updates, np.int32(slot))
        self._kc = new[:L]
        self._vc = new[L:2 * L]
        self._cross = new[2 * L:]
        _M_SCATTER_DISPATCH.inc()

    def join(self, src, prompt, prompt_len=None, max_new_tokens=1):
        """Prefill ONE request into a vacant slot while the rest of the
        batch keeps its decode state. src: [src_len] or [1, src_len];
        prompt likewise. Returns ``(slot, done)`` where ``done`` is None
        while the request decodes, or ``(tokens [n] int64, finished)``
        if it completed at join (budget 1, or the first token is
        end_id). Raises RuntimeError when no slot is vacant — callers
        queue and retry after a ``step`` retires one."""
        s = self._s
        vacant = self.vacant_slots()
        if not vacant:
            raise RuntimeError(
                "no vacant slot (all %d active) — step() until one "
                "retires" % s.batch_size)
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        src = np.ascontiguousarray(src, np.int64).reshape(1, s.src_len)
        prompt = np.ascontiguousarray(prompt,
                                      np.int64).reshape(1, s.prompt_len)
        plen = int(s.prompt_len if prompt_len is None else prompt_len)
        if not 1 <= plen <= s.prompt_len:
            raise ValueError("prompt_len must be in [1, %d], got %d"
                             % (s.prompt_len, plen))
        slot = vacant[0]
        feed = dict(zip(s._prefill1_feeds,
                        [src, prompt, self._pos_src1, self._pos_tgt1,
                         s._causal, np.zeros((1,), np.int32)]
                        + self._zero_caches1))
        outs = s._exe.run(s.prefill1_program, feed=feed,
                          fetch_list=s._prefill1_fetches, scope=s.scope,
                          return_numpy=False)
        first = int(np.asarray(outs[0])[0, plen - 1].argmax())
        _M_SLOT_JOIN.inc()
        if int(max_new_tokens) == 1 or first == s.end_id:
            _M_SLOT_RETIRE.inc()
            return slot, (np.array([first], np.int64), first == s.end_id)
        self._scatter(slot, outs)
        self._tok = jnp.asarray(self._tok).at[slot, 0].set(
            np.int32(first))
        self._fin = jnp.asarray(self._fin).at[slot, 0].set(False)
        self._len = jnp.asarray(self._len).at[slot].set(np.int32(plen))
        self._slots[slot] = _SlotState([first], max_new_tokens)
        return slot, None

    def step(self):
        """ONE decode step of the whole batch. Appends each active
        slot's new token, retires slots that finished or exhausted their
        budget, and returns the completions:
        ``[(slot, tokens [n] int64, finished), ...]``."""
        s = self._s
        if self.active_count == 0:
            raise RuntimeError("step() with no active slot — join first")
        _M_SLOT_OCC.observe(self.active_count / float(s.batch_size))
        self._clamp_idle()
        t0 = time.perf_counter()
        feed = dict(zip(s._decode_feeds,
                        [self._tok, self._fin, s._end_ids, self._len]
                        + list(self._cross) + list(self._kc)
                        + list(self._vc)))
        outs = s._exe.run(s.decode_program, feed=feed,
                          fetch_list=s._decode_fetches, scope=s.scope,
                          return_numpy=False)
        L = s._L
        self._tok, self._len, self._fin = outs[0], outs[1], outs[2]
        self._kc = list(outs[3:3 + L])
        self._vc = list(outs[3 + L:3 + 2 * L])
        _M_DECODE_STEPS.inc()
        _M_DECODE_SECONDS.observe(time.perf_counter() - t0)
        tok_np = np.asarray(self._tok)      # [B,1] — the per-step sync
        fin_np = np.asarray(self._fin)      # the scheduler needs to see
        completed = []
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            st.tokens.append(int(tok_np[slot, 0]))
            finished = bool(fin_np[slot, 0])
            if finished or len(st.tokens) >= st.budget:
                completed.append((slot,
                                  np.array(st.tokens, np.int64),
                                  finished))
                self._slots[slot] = None
                self._fin = jnp.asarray(self._fin).at[slot, 0].set(True)
                _M_SLOT_RETIRE.inc()
        return completed

    def _clamp_idle(self):
        """Pin idle slots to cache_len=1 before each dispatch so a
        long-lived stream never walks their position ids past the
        embedding table (their outputs are discarded; the write keeps
        the ring slot churn bounded too)."""
        idle = np.array([st is None for st in self._slots])
        if idle.any():
            self._len = jnp.where(jnp.asarray(idle), np.int32(1),
                                  jnp.asarray(self._len))


# ---------------------------------------------------------------------------
# Paged decode: shared KV page pool + per-slot page tables + prefix cache.
# ---------------------------------------------------------------------------

class _PagePool:
    """Host-side free list + refcounts over the shared KV page pool.

    Page 0 is the permanently-resident SCRATCH page: every unallocated
    table entry (and every idle slot's whole table) points at it, so
    the shape-closed decode program writes unconditionally — scratch
    contents are garbage by design and are never read through a live
    table entry (attention masks by cache_len)."""

    def __init__(self, n_pages):
        self.n_pages = int(n_pages)
        # pop() takes from the end -> lowest page ids allocated first
        self._free = list(range(self.n_pages - 1, 0, -1))
        self.refs = np.zeros((self.n_pages,), np.int64)

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def live_pages(self):
        return int((self.refs > 0).sum())

    def alloc(self, n):
        """Take ``n`` pages (refcount 1 each) or raise typed
        ``Overloaded`` WITHOUT touching any state — admission control
        for the serving tier, not an assertion."""
        if len(self._free) < n:
            raise Overloaded(
                "KV page pool exhausted: need %d page(s), %d free of %d "
                "usable — retire a stream, shrink prompts, or raise "
                "PADDLE_DECODE_POOL_PAGES"
                % (n, len(self._free), self.n_pages - 1))
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refs[p] = 1
        _M_PAGES_ALLOC.inc(n)
        return pages

    def share(self, pages):
        """Add one reference to each (already live) page — the prefix-
        cache aliasing path."""
        for p in pages:
            assert self.refs[p] > 0, "share of a dead page"
            self.refs[p] += 1
        _M_PAGES_SHARED.inc(len(pages))

    def release(self, pages):
        """Drop one reference per page; pages whose refcount hits zero
        return to the free list."""
        freed = 0
        for p in pages:
            assert self.refs[p] > 0, "release of a dead page"
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
                freed += 1
        if freed:
            _M_PAGES_FREED.inc(freed)


class _PrefixEntry:
    """One cached prompt prefix: the pool pages holding its self-
    attention K/V, the precomputed cross K/V, and the first greedy
    token (everything a hit needs to skip the prefill dispatch)."""

    __slots__ = ("pages", "cross", "first", "plen")

    def __init__(self, pages, cross, first, plen):
        self.pages = tuple(pages)
        self.cross = list(cross)
        self.first = int(first)
        self.plen = int(plen)


class PrefixCache:
    """Content-addressed LRU cache of prefilled prompt prefixes.

    Keyed by sha256 over (src, prompt[:plen], plen) — the compile-cache
    content-hash idiom applied to KV state. The cache holds its own
    refcount on every entry's pages, so a cached prefix stays resident
    after the slot that prefilled it retires; a hit aliases the pages
    into the joining slot's table copy-on-write (the slot splits a
    private copy before its first write to a shared page)."""

    def __init__(self, capacity, pool):
        self.capacity = int(capacity)
        self._pool = pool
        self._entries = collections.OrderedDict()

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def key(src, prompt, plen):
        h = hashlib.sha256()
        h.update(np.int64(plen).tobytes())
        h.update(np.ascontiguousarray(src, np.int64).tobytes())
        h.update(np.ascontiguousarray(
            np.asarray(prompt)[..., :plen], np.int64).tobytes())
        return h.hexdigest()

    def lookup(self, key):
        e = self._entries.get(key)
        if e is not None:
            self._entries.move_to_end(key)
        return e

    def insert(self, key, entry):
        if self.capacity <= 0 or key in self._entries:
            return
        self._pool.share(entry.pages)      # the cache's own reference
        self._entries[key] = entry
        while len(self._entries) > self.capacity:
            _, old = self._entries.popitem(last=False)
            self._pool.release(old.pages)

    def clear(self):
        while self._entries:
            _, old = self._entries.popitem(last=False)
            self._pool.release(old.pages)


def build_paged_decode_session(model, batch_size, src_len, prompt_len,
                               cache_capacity, end_id=1,
                               use_compiled=True, page_tokens=None,
                               pool_pages=None, prefix_cache_size=0):
    """Trace the (batch-1 prefill, paged decode) program pair and wrap
    them in a PagedDecodeSession: a continuous-batching decode stream
    whose per-slot KV state lives in a SHARED page pool indexed by a
    per-slot page table, so HBM scales with LIVE TOKENS (plus page-
    granularity slack) instead of batch x capacity. Two executor
    compiles, like the dense session; join/retire are host page-table
    edits plus one fused scatter, never whole-cache rewrites.

    ``page_tokens`` (default $PADDLE_DECODE_PAGE_TOKENS or 16) is the
    page size in tokens; ``cache_capacity`` must divide into pages.
    ``pool_pages`` (default $PADDLE_DECODE_POOL_PAGES, else every slot
    at full capacity + the scratch page) sizes the pool — undersizing
    it is the point: joins that cannot seat a prompt shed with typed
    ``Overloaded`` instead of silently corrupting. ``prefix_cache_size``
    > 0 keeps that many content-hashed prompt prefixes resident for
    copy-on-write aliasing into later joins. Must run under
    fluid.dygraph.guard(); puts the model in eval() mode."""
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.fluid.executor import Scope

    ptok = int(page_tokens if page_tokens is not None
               else os.environ.get("PADDLE_DECODE_PAGE_TOKENS", "16"))
    if ptok < 1:
        raise ValueError("page_tokens must be >= 1, got %d" % ptok)
    C = int(cache_capacity)
    if C % ptok:
        raise ValueError(
            "cache_capacity=%d must be a multiple of page_tokens=%d"
            % (C, ptok))
    if C < prompt_len:
        raise ValueError(
            "cache_capacity=%d < prompt_len=%d: the prefill write would "
            "cross the ring boundary" % (C, prompt_len))
    B = int(batch_size)
    n_pages = C // ptok
    if pool_pages is None:
        pool_pages = os.environ.get("PADDLE_DECODE_POOL_PAGES")
    P = int(pool_pages) if pool_pages is not None else B * n_pages + 1
    if P < n_pages + 1:
        raise ValueError(
            "pool_pages=%d cannot seat even ONE full slot (%d pages) "
            "plus the scratch page" % (P, n_pages))
    model.eval()
    L = len(model.dec_layers)
    H = model.n_heads
    d = model.d_model // model.n_heads

    prefill1_in = [
        np.zeros((1, src_len), np.int64),
        np.zeros((1, prompt_len), np.int64),
        np.arange(src_len, dtype=np.int64).reshape(1, -1),
        np.arange(prompt_len, dtype=np.int64).reshape(1, -1),
        make_causal_bias(prompt_len),
        np.zeros((1,), np.int32),
    ] + [np.zeros((1, H, C, d), np.float32) for _ in range(2 * L)]
    _, prefill1_tl = dygraph.jit.trace(_MethodShim(model, "prefill"),
                                       prefill1_in)

    decode_in = [
        np.zeros((B, 1), np.int32),
        np.zeros((B, 1), bool),
        np.array([end_id], np.int32),
        np.ones((B,), np.int32),
        np.zeros((B, n_pages), np.int32),
    ] + [np.zeros((B, H, src_len, d), np.float32)
         for _ in range(2 * L)] \
      + [np.zeros((P, H, ptok, d), np.float32) for _ in range(2 * L)]
    _, decode_tl = dygraph.jit.trace(
        _MethodShim(model, "decode_step_paged"), decode_in)

    scope = Scope()
    for _, p in model.named_parameters():
        scope.set_var(p.name, jnp.array(p._ivar, copy=True))
    return PagedDecodeSession(
        prefill1_tl, decode_tl, scope, n_layers=L, batch_size=B,
        src_len=src_len, prompt_len=prompt_len, cache_capacity=C,
        n_heads=H, d_key=d, end_id=end_id, page_tokens=ptok,
        pool_pages=P, use_compiled=use_compiled,
        prefix_cache_size=prefix_cache_size)


class PagedDecodeSession:
    """Continuous-batching greedy decode over PAGED KV state.

    Drives the same (join / step / retire) contract as
    ContinuousDecodeSession — same width/vacant_slots surface, same
    completion tuples — so the serving tier schedules either
    interchangeably. The differences are where the HBM goes and how
    overload surfaces:

    * Self-attention K/V for ALL slots lives in 2L shared pools
      [P, H, page_tokens, d]; each slot owns pages through a
      [B, n_pages] int32 table fed to the decode program every step
      (host-authoritative, like the token/length state). Retiring a
      slot just returns its pages to the free list — no device work.
    * ``join`` sheds with typed ``Overloaded`` when the pool cannot
      seat the prompt (admission control), and RuntimeError when no
      slot is vacant (the caller's retry-after-step signal), matching
      the dense session.
    * A prefix-cache hit skips the prefill dispatch entirely: the new
      slot's table aliases the cached pages and the pool refcounts
      them; ``_ensure_writable`` splits a private copy-on-write page
      the step before the slot would dirty shared state.
    * A slot that needs a page mid-stream when the pool is dry retires
      EARLY (unfinished) rather than corrupting a neighbour — the
      shed-don't-corrupt contract of the serving tier.

    Single-threaded by design, like ContinuousDecodeSession."""

    def __init__(self, prefill1_tl, decode_tl, scope, n_layers,
                 batch_size, src_len, prompt_len, cache_capacity,
                 n_heads, d_key, end_id, page_tokens, pool_pages,
                 use_compiled=True, prefix_cache_size=0):
        self._exe = fluid.Executor()
        self.scope = scope
        self._L = n_layers
        self.batch_size = batch_size
        self.src_len = src_len
        self.prompt_len = prompt_len
        self.cache_capacity = cache_capacity
        self.end_id = int(end_id)
        self.n_heads = n_heads
        self.d_key = d_key
        self.page_tokens = int(page_tokens)
        self.n_pages = cache_capacity // self.page_tokens
        self.pool_pages = int(pool_pages)
        self._use_compiled = bool(use_compiled)
        self._prefill1_feeds = list(prefill1_tl._feed_names)
        self._prefill1_fetches = list(prefill1_tl._fetch_names)
        self._decode_feeds = list(decode_tl._feed_names)
        self._decode_fetches = list(decode_tl._fetch_names)
        if use_compiled:
            self.prefill1_program = fluid.CompiledProgram(
                prefill1_tl.program)
            self.decode_program = fluid.CompiledProgram(decode_tl.program)
        else:
            self.prefill1_program = prefill1_tl.program
            self.decode_program = decode_tl.program
        # raw traced programs, for the liveness (peak-bytes) estimator
        self._prefill1_traced = prefill1_tl.program
        self._decode_traced = decode_tl.program
        B, H, C, d = batch_size, n_heads, cache_capacity, d_key
        P, ptok = self.pool_pages, self.page_tokens
        self.pool = _PagePool(P)
        self.prefix_cache = (PrefixCache(prefix_cache_size, self.pool)
                             if prefix_cache_size else None)
        self._tok = np.full((B, 1), self.end_id, np.int32)
        self._fin = np.ones((B, 1), bool)
        self._len = np.ones((B,), np.int32)
        self._table = np.zeros((B, self.n_pages), np.int32)
        self._kpool = [np.zeros((P, H, ptok, d), np.float32)
                       for _ in range(n_layers)]
        self._vpool = [np.zeros((P, H, ptok, d), np.float32)
                       for _ in range(n_layers)]
        self._cross = [np.zeros((B, H, src_len, d), np.float32)
                       for _ in range(2 * n_layers)]
        self._slots = [None] * B
        self._owned = [[] for _ in range(B)]  # pages each slot refs
        self._zero_caches1 = [np.zeros((1, H, C, d), np.float32)
                              for _ in range(2 * n_layers)]
        self._pos_src1 = np.arange(src_len,
                                   dtype=np.int64).reshape(1, -1)
        self._pos_tgt1 = np.arange(prompt_len,
                                   dtype=np.int64).reshape(1, -1)
        self._causal = make_causal_bias(prompt_len)
        self._end_ids = np.array([self.end_id], np.int32)

    @property
    def width(self):
        return self.batch_size

    @property
    def active_count(self):
        return sum(st is not None for st in self._slots)

    def vacant_slots(self):
        return [i for i, st in enumerate(self._slots) if st is None]

    def live_tokens(self):
        """Host bookkeeping: tokens resident across all active slots."""
        return int(sum(min(int(self._len[b]), self.cache_capacity)
                       for b, st in enumerate(self._slots)
                       if st is not None))

    def join(self, src, prompt, prompt_len=None, max_new_tokens=1):
        """Admit ONE request. Same contract as
        ContinuousDecodeSession.join — ``(slot, done)``, RuntimeError
        when no slot is vacant — plus typed ``Overloaded`` when the
        page pool cannot seat the prompt (shed, don't queue). On a
        prefix-cache hit the prefill dispatch is skipped: the slot's
        table aliases the cached pages copy-on-write."""
        vacant = self.vacant_slots()
        if not vacant:
            raise RuntimeError(
                "no vacant slot (all %d active) — step() until one "
                "retires" % self.batch_size)
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        src = np.ascontiguousarray(src, np.int64).reshape(
            1, self.src_len)
        prompt = np.ascontiguousarray(prompt, np.int64).reshape(
            1, self.prompt_len)
        plen = int(self.prompt_len if prompt_len is None else prompt_len)
        if not 1 <= plen <= self.prompt_len:
            raise ValueError("prompt_len must be in [1, %d], got %d"
                             % (self.prompt_len, plen))
        slot = vacant[0]
        ptok = self.page_tokens
        n_prompt_pages = -(-plen // ptok)
        L = self._L
        key = entry = None
        if self.prefix_cache is not None:
            key = PrefixCache.key(src, prompt, plen)
            entry = self.prefix_cache.lookup(key)
        if entry is not None:
            _M_PREFIX_HIT.inc()
            _M_SLOT_JOIN.inc()
            first = entry.first
            if int(max_new_tokens) == 1 or first == self.end_id:
                _M_SLOT_RETIRE.inc()
                return slot, (np.array([first], np.int64),
                              first == self.end_id)
            self.pool.share(entry.pages)
            self._owned[slot] = list(entry.pages)
            self._table[slot, :] = 0
            self._table[slot, :n_prompt_pages] = entry.pages
            self._cross = _slot_scatter(
                [jnp.asarray(a) for a in self._cross],
                [jnp.asarray(c) for c in entry.cross],
                np.int32(slot))
        else:
            if self.prefix_cache is not None:
                _M_PREFIX_MISS.inc()
            # reserve pages BEFORE the prefill dispatch so an exhausted
            # pool sheds without wasting device work
            pages = self.pool.alloc(n_prompt_pages)
            feed = dict(zip(self._prefill1_feeds,
                            [src, prompt, self._pos_src1,
                             self._pos_tgt1, self._causal,
                             np.zeros((1,), np.int32)]
                            + self._zero_caches1))
            outs = self._exe.run(self.prefill1_program, feed=feed,
                                 fetch_list=self._prefill1_fetches,
                                 scope=self.scope, return_numpy=False)
            first = int(np.asarray(outs[0])[0, plen - 1].argmax())
            _M_SLOT_JOIN.inc()
            if int(max_new_tokens) == 1 or first == self.end_id:
                self.pool.release(pages)
                _M_SLOT_RETIRE.inc()
                return slot, (np.array([first], np.int64),
                              first == self.end_id)
            self._owned[slot] = list(pages)
            self._table[slot, :] = 0
            self._table[slot, :n_prompt_pages] = pages
            rows = np.zeros((self.n_pages,), np.int32)
            rows[:n_prompt_pages] = pages
            packed = _paged_pack(
                [jnp.asarray(p) for p in self._kpool + self._vpool],
                [jnp.asarray(c) for c in outs[1:1 + 2 * L]],
                rows)
            self._kpool = packed[:L]
            self._vpool = packed[L:]
            cross1 = [jnp.asarray(c) for c in outs[1 + 2 * L:1 + 4 * L]]
            self._cross = _slot_scatter(
                [jnp.asarray(a) for a in self._cross], cross1,
                np.int32(slot))
            if self.prefix_cache is not None:
                self.prefix_cache.insert(key, _PrefixEntry(
                    pages, cross1, first, plen))
        self._tok[slot, 0] = first
        self._fin[slot, 0] = False
        self._len[slot] = plen
        self._slots[slot] = _SlotState([first], max_new_tokens)
        return slot, None

    def step(self):
        """ONE decode step of the whole batch — the
        ContinuousDecodeSession.step contract. Before the dispatch,
        every active slot's next write position is made exclusively
        writable (first-touch page allocation, copy-on-write splits);
        slots the pool cannot serve retire early, UNFINISHED, into the
        returned completions."""
        if self.active_count == 0:
            raise RuntimeError("step() with no active slot — join first")
        _M_SLOT_OCC.observe(self.active_count / float(self.batch_size))
        completed = []
        self._clamp_idle()
        self._ensure_writable(completed)
        if self.active_count == 0:
            return completed
        t0 = time.perf_counter()
        feed = dict(zip(self._decode_feeds,
                        [self._tok, self._fin, self._end_ids, self._len,
                         self._table]
                        + list(self._cross) + list(self._kpool)
                        + list(self._vpool)))
        outs = self._exe.run(self.decode_program, feed=feed,
                             fetch_list=self._decode_fetches,
                             scope=self.scope, return_numpy=False)
        L = self._L
        self._kpool = list(outs[3:3 + L])
        self._vpool = list(outs[3 + L:3 + 2 * L])
        _M_DECODE_STEPS.inc()
        _M_DECODE_SECONDS.observe(time.perf_counter() - t0)
        tok_np = np.asarray(outs[0])        # [B,1] — the per-step sync
        fin_np = np.asarray(outs[2])
        # token/length/finished state stays HOST-authoritative (numpy):
        # the page table lives there anyway, and retires must mutate it
        self._tok = np.array(tok_np, np.int32)
        self._fin = np.array(fin_np, bool)
        self._len = self._len + 1           # mirrors in-graph new_len
        for slot, st in enumerate(self._slots):
            if st is None:
                continue
            st.tokens.append(int(tok_np[slot, 0]))
            finished = bool(fin_np[slot, 0])
            if finished or len(st.tokens) >= st.budget:
                completed.append((slot, np.array(st.tokens, np.int64),
                                  finished))
                self._retire(slot)
                _M_SLOT_RETIRE.inc()
        return completed

    def _retire(self, slot):
        self._slots[slot] = None
        self._fin[slot, 0] = True
        self._tok[slot, 0] = self.end_id
        if self._owned[slot]:
            self.pool.release(self._owned[slot])
            self._owned[slot] = []
        self._table[slot, :] = 0

    def _shed(self, slot, completed):
        """Early-retire ``slot`` (unfinished) because the pool could
        not serve its next write — degraded completion beats corrupting
        a shared page."""
        st = self._slots[slot]
        completed.append((slot, np.array(st.tokens, np.int64), False))
        self._retire(slot)
        _M_SLOT_RETIRE.inc()

    def _ensure_writable(self, completed):
        """Make every active slot's NEXT write position land on a page
        it exclusively owns: allocate on first touch (ring growth past
        the prompt pages), split copy-on-write when the page is shared
        with the prefix cache. Runs before each dispatch; the write
        position is host-known (len % C), so this is pure host
        bookkeeping plus at most one fused device copy per split."""
        ptok, C = self.page_tokens, self.cache_capacity
        for b, st in enumerate(self._slots):
            if st is None:
                continue
            j = (int(self._len[b]) % C) // ptok
            page = int(self._table[b, j])
            if page == 0:
                try:
                    (new,) = self.pool.alloc(1)
                except Overloaded:
                    self._shed(b, completed)
                    continue
                self._table[b, j] = new
                self._owned[b].append(new)
            elif self.pool.refs[page] > 1:
                try:
                    (new,) = self.pool.alloc(1)
                except Overloaded:
                    self._shed(b, completed)
                    continue
                pools = _paged_cow(
                    [jnp.asarray(a)
                     for a in self._kpool + self._vpool],
                    np.int32(page), np.int32(new))
                self._kpool = pools[:self._L]
                self._vpool = pools[self._L:]
                self._table[b, j] = new
                self._owned[b][self._owned[b].index(page)] = new
                self.pool.release([page])

    def _clamp_idle(self):
        for b, st in enumerate(self._slots):
            if st is None:
                self._len[b] = 1


# ---------------------------------------------------------------------------
# Speculative decoding: shallow self-draft proposes, target verifies k
# tokens per dispatch with greedy accept/rollback.
# ---------------------------------------------------------------------------

def build_speculative_session(model, session, k=4, draft_layers=None):
    """Wrap a dense DecodeSession in a SpeculativeDecodeSession: a
    SELF-speculative draft (the first ``draft_layers`` decoder layers +
    the shared embeddings and output projection — no second model, no
    extra parameters) proposes ``k`` tokens per round, and the full
    target verifies all k in ONE decode dispatch (q_len=k with the
    per-row causal window), accepting the longest matching greedy
    prefix. Exactly TWO additional executor compiles (draft step +
    verify step) on top of the base pair — asserted via the compile-
    cache counter in bench/tests. Greedy output is token-identical to
    ``session.generate``: the draft only changes which positions the
    target computes in parallel, never which tokens are accepted. Must
    run under fluid.dygraph.guard() with the model the session was
    built from."""
    from paddle_tpu.fluid import dygraph

    k = int(k)
    if k < 2:
        raise ValueError(
            "speculative k must be >= 2 (k=1 is the plain decode step)")
    L = session._L
    Ld = int(draft_layers) if draft_layers is not None else max(1, L // 2)
    if not 1 <= Ld <= L:
        raise ValueError("draft_layers must be in [1, %d], got %d"
                         % (L, Ld))
    model.eval()
    s = session
    B, H, C, d = s.batch_size, s.n_heads, s.cache_capacity, s.d_key
    draft_in = [
        np.zeros((B, 1), np.int32),
        np.zeros((B, 1), bool),
        np.array([s.end_id], np.int32),
        np.ones((B,), np.int32),
    ] + [np.zeros((B, H, s.src_len, d), np.float32)
         for _ in range(2 * Ld)] \
      + [np.zeros((B, H, C, d), np.float32) for _ in range(2 * Ld)]
    _, draft_tl = dygraph.jit.trace(
        _MethodShim(model, "decode_step_draft"), draft_in)
    verify_in = [
        np.zeros((B, k), np.int32),
        np.arange(k, dtype=np.int32).reshape(1, -1),
        np.ones((B,), np.int32),
    ] + [np.zeros((B, H, s.src_len, d), np.float32)
         for _ in range(2 * L)] \
      + [np.zeros((B, H, C, d), np.float32) for _ in range(2 * L)]
    _, verify_tl = dygraph.jit.trace(
        _MethodShim(model, "verify_step"), verify_in)
    return SpeculativeDecodeSession(session, draft_tl, verify_tl, k, Ld)


class SpeculativeDecodeSession:
    """Greedy speculative decoding over a base DecodeSession.

    Per round: the draft runs k single-token dispatches (k-1 proposals
    plus one ingest, so its cache never holds a gap), then the target
    verifies the whole k-token window in ONE dispatch and the host
    accepts the longest prefix where the draft's proposal matches the
    target's greedy choice — so each TARGET dispatch emits between 1
    and k tokens instead of exactly 1. Rollback is a host-side length
    edit: rejected cache rows sit above the rolled-back length, masked
    until overwritten, which is why generations must never wrap the KV
    ring (asserted in generate)."""

    def __init__(self, session, draft_tl, verify_tl, k, draft_layers):
        self._s = session
        self.k = int(k)
        self.draft_layers = int(draft_layers)
        self._draft_feeds = list(draft_tl._feed_names)
        self._draft_fetches = list(draft_tl._fetch_names)
        self._verify_feeds = list(verify_tl._feed_names)
        self._verify_fetches = list(verify_tl._fetch_names)
        if session._use_compiled:
            self.draft_program = fluid.CompiledProgram(draft_tl.program)
            self.verify_program = fluid.CompiledProgram(verify_tl.program)
        else:
            self.draft_program = draft_tl.program
            self.verify_program = verify_tl.program
        self._step_ids = np.arange(self.k, dtype=np.int32).reshape(1, -1)

    def generate(self, src, prompt, prompt_lens, max_new_tokens):
        """Drop-in for DecodeSession.generate — same arguments, same
        greedy tokens, fewer target dispatches. Requires
        max(prompt_lens) + max_new_tokens + k <= cache_capacity: the
        verify window must never wrap the ring (rollback only moves the
        length pointer, which is sound only while every stale row sits
        ABOVE it)."""
        s, k, Ld, L = self._s, self.k, self.draft_layers, self._s._L
        B = s.batch_size
        src = np.ascontiguousarray(src, np.int64)
        prompt = np.ascontiguousarray(prompt, np.int64)
        plens = np.asarray(prompt_lens, np.int64).reshape(B)
        if src.shape != (B, s.src_len) or \
                prompt.shape != (B, s.prompt_len):
            raise ValueError(
                "shape mismatch: session traced for src %s / prompt %s, "
                "got %s / %s — pad or re-trace" %
                ((B, s.src_len), (B, s.prompt_len), src.shape,
                 prompt.shape))
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if plens.min() < 1 or plens.max() > s.prompt_len:
            raise ValueError("prompt_lens must be in [1, %d]"
                             % s.prompt_len)
        if int(plens.max()) + int(max_new_tokens) + k > s.cache_capacity:
            raise ValueError(
                "speculative decode must not wrap the KV ring: "
                "max prompt_len %d + max_new_tokens %d + k %d > "
                "cache_capacity %d"
                % (plens.max(), max_new_tokens, k, s.cache_capacity))

        # target prefill — the base session's compiled program
        feed = dict(zip(s._prefill_feeds,
                        [src, prompt, s._pos_src, s._pos_tgt, s._causal,
                         np.zeros((B,), np.int32)] + s._zero_caches))
        outs = s._exe.run(s.prefill_program, feed=feed,
                          fetch_list=s._prefill_fetches, scope=s.scope,
                          return_numpy=False)
        logits = np.asarray(outs[0])
        kc = list(outs[1:1 + L])
        vc = list(outs[1 + L:1 + 2 * L])
        cross = list(outs[1 + 2 * L:1 + 4 * L])
        dcross = cross[:Ld] + cross[L:L + Ld]

        first = logits[np.arange(B), plens - 1, :].argmax(-1) \
            .astype(np.int32)
        cur = first[:, None].copy()          # [B,1] pending token
        emitted = [[int(t)] for t in first]
        fin = first == s.end_id              # [B] host finished mask
        tlen = plens.astype(np.int32)        # target cache length
        need = int(max_new_tokens)

        # draft prompt ingestion: replay the prompt through the ONE
        # compiled draft program (no extra compile), one position per
        # dispatch; rows shorter than the longest prompt idempotently
        # rewrite their last prompt position
        H, C, d = s.n_heads, s.cache_capacity, s.d_key
        dkc = [np.zeros((B, H, C, d), np.float32) for _ in range(Ld)]
        dvc = [np.zeros((B, H, C, d), np.float32) for _ in range(Ld)]
        no_fin = np.zeros((B, 1), bool)
        rows = np.arange(B)
        for t in range(int(plens.max())):
            lens_t = np.minimum(t, plens - 1).astype(np.int32)
            toks_t = prompt[rows, lens_t].astype(np.int32)[:, None]
            feed = dict(zip(self._draft_feeds,
                            [toks_t, no_fin, s._end_ids, lens_t]
                            + dcross + dkc + dvc))
            outs = s._exe.run(self.draft_program, feed=feed,
                              fetch_list=self._draft_fetches,
                              scope=s.scope, return_numpy=False)
            dkc = list(outs[3:3 + Ld])
            dvc = list(outs[3 + Ld:3 + 2 * Ld])
        dlen = tlen.copy()

        while any(len(emitted[b]) < need and not fin[b]
                  for b in range(B)):
            # draft: k-1 proposals + 1 ingest of the last proposal
            d_toks = [cur.copy()]
            dt = cur
            for _ in range(k - 1):
                feed = dict(zip(self._draft_feeds,
                                [dt, no_fin, s._end_ids, dlen]
                                + dcross + dkc + dvc))
                outs = s._exe.run(self.draft_program, feed=feed,
                                  fetch_list=self._draft_fetches,
                                  scope=s.scope, return_numpy=False)
                dt = np.array(np.asarray(outs[0]), np.int32)
                dkc = list(outs[3:3 + Ld])
                dvc = list(outs[3 + Ld:3 + 2 * Ld])
                dlen = dlen + 1
                d_toks.append(dt)
            feed = dict(zip(self._draft_feeds,
                            [dt, no_fin, s._end_ids, dlen]
                            + dcross + dkc + dvc))
            outs = s._exe.run(self.draft_program, feed=feed,
                              fetch_list=self._draft_fetches,
                              scope=s.scope, return_numpy=False)
            dkc = list(outs[3:3 + Ld])
            dvc = list(outs[3 + Ld:3 + 2 * Ld])

            # target: verify the whole window in ONE dispatch
            toks = np.concatenate(d_toks, axis=1)      # [B, k] int32
            feed = dict(zip(self._verify_feeds,
                            [toks, self._step_ids, tlen]
                            + cross + kc + vc))
            outs = s._exe.run(self.verify_program, feed=feed,
                              fetch_list=self._verify_fetches,
                              scope=s.scope, return_numpy=False)
            g = np.asarray(outs[0])                    # [B, k] greedy
            kc = list(outs[2:2 + L])
            vc = list(outs[2 + L:2 + 2 * L])

            new_tlen = tlen.copy()
            for b in range(B):
                if len(emitted[b]) >= need or fin[b]:
                    continue        # frozen: length pinned, writes inert
                a = 1
                while a < k and int(toks[b, a]) == int(g[b, a - 1]):
                    a += 1
                _M_SPEC_ACCEPT.observe(a)
                for t in g[b, :a]:
                    t = s.end_id if fin[b] else int(t)
                    emitted[b].append(t)
                    if t == s.end_id:
                        fin[b] = True
                    if len(emitted[b]) >= need:
                        break
                cur[b, 0] = g[b, a - 1]
                new_tlen[b] = tlen[b] + a
            tlen = new_tlen
            dlen = tlen.copy()      # draft rollback rides the target's

        tokens = np.full((B, need), s.end_id, np.int64)
        for b in range(B):
            t = emitted[b][:need]
            tokens[b, :len(t)] = t
        _M_DECODE_CACHE.set(float(np.minimum(
            plens + need, s.cache_capacity).sum()))
        return tokens, fin.copy()
