"""4D hybrid-parallel transformer LM train step: dp × pp × tp × sp.

This is the capstone the reference cannot express (its 2019 stack has DP +
section-pipeline only, SURVEY §2.5): one SPMD program over a 4-axis mesh
combining
  dp — batch sharding, gradient psum
  pp — GPipe stages via ppermute (``pipeline_sharded``)
  tp — Megatron column/row-parallel attention + FFN with f/g boundary ops
  sp — ring attention over the sequence dimension (``ring_attention_sharded``)
differentiated end-to-end by ``jax.grad`` — the backward pipeline schedule,
attention ring reversal, and tp reductions all fall out of AD + collective
VJPs. SGD update applied in-shard (params never leave their shards).

Gradient-sync rules (derived, and locked in by
``tests/test_hybrid_parallel.py`` against a single-device reference):
  * all grads psum over (dp, sp) — tokens are sharded there;
  * embed/pos/head additionally psum over pp — input path lives on the
    first stage, head path on the last;
  * nothing over tp — the f/g ops already settle tp cotangents.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .attention import ring_attention_sharded
from .mesh import make_mesh
from .pipeline import pipeline_sharded
from .tp import copy_to_tp_region, pmean_exact, reduce_from_tp_region


class HybridConfig:
    def __init__(self, vocab=1024, hidden=64, n_heads=8, ffn=128,
                 layers_per_stage=2, seq_len=64, microbatches=2):
        self.vocab = vocab
        self.hidden = hidden
        self.n_heads = n_heads
        self.ffn = ffn
        self.layers_per_stage = layers_per_stage
        self.seq_len = seq_len
        self.microbatches = microbatches


def choose_axes(n_devices):
    """Factor n devices into {dp, pp, tp, sp}: innermost axes first get 2
    (sp and tp carry per-step collectives and want ICI neighbors)."""
    sizes = {"sp": 1, "tp": 1, "pp": 1, "dp": 1}
    rem = n_devices
    for ax in ("sp", "tp", "pp"):
        if rem % 2 == 0 and rem >= 2:
            sizes[ax] = 2
            rem //= 2
    sizes["dp"] = rem
    return sizes


def init_params(cfg, n_stages, tp_size, seed=0):
    """Global (unsharded) param pytree; leaves carry a leading [pp] stage
    dim for stage params. Shapes are the full logical shapes — sharding
    happens via in_specs."""
    rng = np.random.RandomState(seed)
    h, f, l, s = cfg.hidden, cfg.ffn, cfg.layers_per_stage, n_stages

    def w(*shape, scale=None):
        scale = scale or (1.0 / np.sqrt(shape[-2]) if len(shape) >= 2 else 0.02)
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale)

    return {
        "emb": w(cfg.vocab, h, scale=0.02),
        "pos": w(cfg.seq_len, h, scale=0.02),
        "head": w(h, cfg.vocab),
        "stages": {
            "ln1_g": jnp.ones((s, l, h), jnp.float32),
            "ln1_b": jnp.zeros((s, l, h), jnp.float32),
            "ln2_g": jnp.ones((s, l, h), jnp.float32),
            "ln2_b": jnp.zeros((s, l, h), jnp.float32),
            "wq": w(s, l, h, h),
            "wk": w(s, l, h, h),
            "wv": w(s, l, h, h),
            "wo": w(s, l, h, h),
            "w1": w(s, l, h, f),
            "b1": jnp.zeros((s, l, f), jnp.float32),
            "w2": w(s, l, f, h),
            "b2": jnp.zeros((s, l, h), jnp.float32),
        },
    }


def param_specs():
    """PartitionSpec per leaf (matching init_params layout)."""
    return {
        "emb": P(),
        "pos": P("sp", None),
        "head": P(),
        "stages": {
            "ln1_g": P("pp", None, None),
            "ln1_b": P("pp", None, None),
            "ln2_g": P("pp", None, None),
            "ln2_b": P("pp", None, None),
            "wq": P("pp", None, None, "tp"),
            "wk": P("pp", None, None, "tp"),
            "wv": P("pp", None, None, "tp"),
            "wo": P("pp", None, "tp", None),
            "w1": P("pp", None, None, "tp"),
            "b1": P("pp", None, "tp"),
            "w2": P("pp", None, "tp", None),
            "b2": P("pp", None, None),
        },
    }


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _block(x, p, i, cfg, heads_local):
    """One transformer layer, tp-sharded weights, sp-ring attention.
    x: [mb, s_local, H]."""
    d = cfg.hidden // cfg.n_heads
    h = _ln(x, p["ln1_g"][i], p["ln1_b"][i])
    h = copy_to_tp_region(h, "tp")
    mb, sl, _ = h.shape

    def split(w):
        y = h @ w[i]  # [mb, s_local, H/tp]
        return y.reshape(mb, sl, heads_local, d)

    q, k, v = split(p["wq"]), split(p["wk"]), split(p["wv"])
    attn = ring_attention_sharded(q, k, v, "sp", causal=True)
    attn = attn.reshape(mb, sl, heads_local * d)
    x = x + reduce_from_tp_region(attn @ p["wo"][i], "tp")

    h2 = _ln(x, p["ln2_g"][i], p["ln2_b"][i])
    h2 = copy_to_tp_region(h2, "tp")
    f1 = jax.nn.relu(h2 @ p["w1"][i] + p["b1"][i])
    return x + reduce_from_tp_region(f1 @ p["w2"][i], "tp") + p["b2"][i]


def _stage_fn(cfg, heads_local, stage_params, x):
    for i in range(cfg.layers_per_stage):
        x = _block(x, stage_params, i, cfg, heads_local)
    return x


def _loss_sharded(params, ids, labels, cfg, tp_size):
    """Per-shard global-mean LM loss. ids/labels: [b_local, s_local]."""
    heads_local = cfg.n_heads // tp_size
    pp_n = jax.lax.axis_size("pp")
    pp_rank = jax.lax.axis_index("pp")

    x = params["emb"][ids] + params["pos"][None, :, :]
    m = cfg.microbatches
    b_local, s_local = ids.shape
    mbs = x.reshape(m, b_local // m, s_local, cfg.hidden)

    stage = functools.partial(_stage_fn, cfg, heads_local)
    # per-shard stage leaves are [1, L, ...] (pp dim sharded): drop the dim
    local_stages = jax.tree_util.tree_map(lambda x: x[0], params["stages"])
    out = pipeline_sharded(stage, local_stages, mbs, "pp")
    out = out.reshape(b_local, s_local, cfg.hidden)

    logits = out @ params["head"]  # [b_local, s_local, V]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ce = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    loss = jnp.mean(ce)
    # valid on the last pp rank only -> broadcast over pp, average tokens.
    # NOTE: raw psum/pmean here would transpose to psum under
    # check_vma=False, scaling grads by the axis size — use the exact-VJP
    # collectives (tp.py) for every reduction inside the differentiated step.
    loss = reduce_from_tp_region(
        jnp.where(pp_rank == pp_n - 1, loss, 0.0), "pp")
    return pmean_exact(pmean_exact(loss, "dp"), "sp")


def _sync_grads(grads):
    g = jax.tree_util.tree_map(
        lambda x: jax.lax.psum(jax.lax.psum(x, "dp"), "sp"), grads)
    # pos rows are sp-SHARDED (each sp rank owns its rows): dp-sum only
    g["pos"] = jax.lax.psum(grads["pos"], "dp")
    for k in ("emb", "pos", "head"):
        g[k] = jax.lax.psum(g[k], "pp")
    return g


def make_train_step(cfg, mesh, lr=0.1):
    """Returns jitted train_step(params, ids, labels) -> (params, loss) over
    the 4-axis mesh. ids/labels: [B, S] global int32."""
    tp_size = dict(mesh.shape).get("tp", 1)

    def step(params, ids, labels):
        def loss_fn(p):
            return _loss_sharded(p, ids, labels, cfg, tp_size)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = _sync_grads(grads)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                        grads)
        return params, loss

    specs = param_specs()
    data_spec = P("dp", "sp")
    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(specs, data_spec, data_spec),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0,))


def reference_loss(params, ids, labels, cfg):
    """Single-device forward (no mesh): the numeric ground truth."""
    d = cfg.hidden // cfg.n_heads
    x = params["emb"][ids] + params["pos"][None, :, :]
    st = params["stages"]
    n_stages = st["wq"].shape[0]
    for s in range(n_stages):
        for i in range(cfg.layers_per_stage):
            h = _ln(x, st["ln1_g"][s, i], st["ln1_b"][s, i])
            b, sl, _ = h.shape
            q = (h @ st["wq"][s, i]).reshape(b, sl, cfg.n_heads, d)
            k = (h @ st["wk"][s, i]).reshape(b, sl, cfg.n_heads, d)
            v = (h @ st["wv"][s, i]).reshape(b, sl, cfg.n_heads, d)
            from .attention import attention_reference

            attn = attention_reference(q, k, v, causal=True)
            x = x + attn.reshape(b, sl, cfg.hidden) @ st["wo"][s, i]
            h2 = _ln(x, st["ln2_g"][s, i], st["ln2_b"][s, i])
            f1 = jax.nn.relu(h2 @ st["w1"][s, i] + st["b1"][s, i])
            x = x + f1 @ st["w2"][s, i] + st["b2"][s, i]
    logits = x @ params["head"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    ce = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return jnp.mean(ce)


def demo_batch(cfg, batch, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab, (batch, cfg.seq_len)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (batch, cfg.seq_len)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(labels)
