"""The comparison that decides ``correct`` for a training cell, and the
plain reference's side of it.

A family gives a plain loss function ``loss(params, feed, mm)`` in float32
``jax.numpy``; this file drives it through three Adam steps (Paddle's form
of Adam, written out here, not imported), and reduces both sides to the
numbers compared:

* ``loss_gap``  - each of the three steps' loss, worst relative gap;
* ``grad_gap``  - the norm of the first gradient as the optimizer got it,
  leaf by leaf (the program's is read back from Adam's first moment after
  step one: ``m1 = (1 - beta1) * g``), worst leaf;
* ``delta_gap`` - the norm of each leaf's change over the three steps,
  worst leaf, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``grad_mid``, ``delta_mid`` - the same two, the median leaf's gap in
  place of the worst: the worst leaf swings from seed to seed, so the
  mildest fp8 sits within three times of a sound run's worst leaf in some
  cells, while the median leaf keeps the two well apart. The worst-leaf
  numbers stay to catch what touches one leaf only.

A leaf's gap is ``|norm_program - norm_reference|`` over the larger of
the reference's norm of that leaf and of the median leaf.

Nothing here imports the program. Parameters are dicts ``name -> array``;
a name that starts with ``layers.`` is stacked over the layers on axis 0,
and ``name[i]`` is how a single layer's leaf is called on both sides.
"""

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STEPS = 3
NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "grad_mid", "delta_mid")


# -- precisions: the reference's own, and the control's ----------------------
def round_fp8(x):
    """Round float32 to 3 bits of mantissa, to nearest even: an fp8
    (e4m3) value under an ideal scale, so the mildest fp8 there is."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(0x7FFFF) + ((u >> 20) & jnp.uint32(1))) \
        & jnp.uint32(0xFFF00000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@jax.custom_vjp
def _as_fp8(x):
    """A tensor held in fp8: rounded going forward, and its cotangent
    rounded going back."""
    return round_fp8(x)


_as_fp8.defvjp(lambda x: (round_fp8(x), None),
               lambda _, g: (round_fp8(g),))


def matmul(precision):
    """``mm(eq, a, b)``: an einsum at ``highest``. Under ``fp8`` the
    matmul's operands and its result are held in fp8, forward and
    backward: what the configuration's AMP policy (low-precision matmuls,
    in and out, the activations after them following) gives one type down,
    as ``mixed_precision.decorate(dest_dtype=<an fp8>)`` would."""
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: _as_fp8(jnp.einsum(
            eq, _as_fp8(a), _as_fp8(b), precision=HIGHEST))
    raise ValueError("no such reference precision: %r" % (precision,))


# -- what every family's yardstick shares -------------------------------------
def seed_key(seed):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_norm(x, w, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def half_batch(feed, rows_of="src_ids"):
    """The planted fault "half of the batch left out": in every array that
    has a row for each of ``feed[rows_of]``'s, the first half of the rows
    stands in the second half's place, so the mean is over the first."""
    b = feed[rows_of].shape[0]
    h = b // 2
    out = {}
    for k, v in feed.items():
        v = np.array(v)
        if v.shape[0] == b:
            v[h:2 * h] = v[:h]
        out[k] = v
    return out


# -- leaves ------------------------------------------------------------------
def unstack(params):
    """``{"layers.q_w": [L, ...]}`` -> ``{"layers.q_w[0]": [...], ...}``."""
    flat = {}
    for k, v in params.items():
        if k.startswith("layers."):
            for i in range(v.shape[0]):
                flat["%s[%d]" % (k, i)] = v[i]
        else:
            flat[k] = v
    return flat


@jax.jit
def leaf_norms(flat):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in flat.items()}


@jax.jit
def leaf_delta_norms(flat, flat0):
    return leaf_norms({k: flat[k].astype(jnp.float32) - flat0[k]
                       for k in flat})


def to_floats(d):
    return {k: float(v) for k, v in jax.device_get(d).items()}


# -- the reference's three steps ---------------------------------------------
def reference_steps(loss_fn, params, feeds, opt, half_batch=None):
    """Three Adam steps of ``loss_fn(params, feed)`` from ``params``.
    Returns ``{"loss": [3], "grad": {leaf: norm}, "delta": {leaf: norm}}``.
    ``half_batch``, a planted fault: a function of the feed that leaves
    half of the rows out."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, feed):
        loss, g = jax.value_and_grad(loss_fn)(p, feed)
        gn = leaf_norms(unstack(g))
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in p}
        p = {k: p[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps) for k in p}
        return loss, gn, p, m, v

    p = {k: jnp.array(v, jnp.float32) for k, v in params.items()}
    m = {k: jnp.zeros_like(x) for k, x in p.items()}
    v = {k: jnp.zeros_like(x) for k, x in p.items()}
    out = {"loss": []}
    for i, feed in enumerate(feeds[:STEPS]):
        if half_batch is not None:
            feed = half_batch(feed)
        loss, gn, p, m, v = step(p, m, v, jnp.float32(i + 1), feed)
        out["loss"].append(float(loss))
        if i == 0:
            out["grad"] = to_floats(gn)
    out["delta"] = to_floats(leaf_delta_norms(unstack(p), unstack(params)))
    return out


# -- the numbers compared ----------------------------------------------------
def _leaf_gaps(got, ref, keep):
    """``(worst gap, its leaf, median gap)`` over the leaves in ``keep``."""
    med = statistics.median(ref.values())
    worst, leaf, all_gaps = 0.0, None, []
    for k in keep:
        gap = abs(got[k] - ref[k]) / max(ref[k], med)
        if not gap <= worst:        # a NaN gap is the worst there is
            worst, leaf = gap, k
        all_gaps.append(gap if gap == gap else float("inf"))
    return float(worst), leaf, float(statistics.median(all_gaps))


def gaps(got, ref):
    """``got`` and ``ref`` as ``reference_steps`` returns them. Returns
    ``({number: gap}, {number: where})``."""
    assert set(got["grad"]) == set(ref["grad"]), (
        sorted(set(got["grad"]) ^ set(ref["grad"])))
    num, where = {}, {}
    rel = [abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"])]
    bad = [i for i, r in enumerate(rel) if not np.isfinite(r)]
    num["loss_gap"] = float("inf") if bad else float(max(rel))
    where["loss_gap"] = "step %d" % (1 + (bad[0] if bad
                                          else int(np.argmax(rel))))
    num["grad_gap"], where["grad_gap"], num["grad_mid"] = _leaf_gaps(
        got["grad"], ref["grad"], sorted(ref["grad"]))
    floor = 1e-3 * statistics.median(ref["grad"].values())
    moved = sorted(k for k, g in ref["grad"].items() if g >= floor)
    num["delta_gap"], where["delta_gap"], num["delta_mid"] = _leaf_gaps(
        got["delta"], ref["delta"], moved)
    where["grad_mid"] = "median of %d leaves" % len(ref["grad"])
    where["delta_mid"] = "median of %d leaves" % len(moved)
    return num, where


def judge(num, limits):
    """``(correct, [[name, number, limit], ...])``; a number that is not
    finite, or a limit that is missing, is not correct."""
    rows = [[k, num[k], limits.get(k)] for k in NUMBERS if k in limits]
    ok = bool(rows) and all(
        lim is not None and np.isfinite(x) and x <= lim for _, x, lim in rows)
    return ok, rows
