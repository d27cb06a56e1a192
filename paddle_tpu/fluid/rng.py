"""Root PRNG key policy.

Dropout/random-op keys derive from one root key per scope. The impl
matters enormously on TPU: threefry (jax's default) computes its hash on
the VPU and costs ~25% of a BERT-base training step in dropout masks;
the hardware ``rbg`` generator is ~free (measured on v5e: 135.7 ->
100.8 ms/step). ``unsafe_rbg`` additionally makes the per-op key
*derivation* (split/fold_in, ~25 per BERT step) trivial instead of
threefry-strength — measured 94.8 -> 87.5 ms/step — and is the TPU
default: dropout-mask randomness needs statistical quality from the
generator, not cryptographic key separation (the reference's per-op
curand Philox seeding makes the same trade). CPU and tests keep
threefry (bit-reproducibility with stock jax); override with
PADDLE_TPU_PRNG=threefry|rbg|unsafe_rbg.

The impl rides WITH the key (``jax.random.key(seed, impl=...)``), so no
global config flips and mixed-impl processes stay coherent.
"""

import os

__all__ = ["root_key", "key_data", "wrap_key_data"]


_ALIASES = {"threefry": "threefry2x32", "threefry2x32": "threefry2x32",
            "rbg": "rbg", "unsafe_rbg": "unsafe_rbg"}
_IMPL = None  # resolved once: raw key data must wrap under ONE impl


def _impl():
    global _IMPL
    if _IMPL is not None:
        return _IMPL
    env = os.environ.get("PADDLE_TPU_PRNG")
    if env:
        if env not in _ALIASES:
            raise ValueError(
                "PADDLE_TPU_PRNG=%r; expected one of %s"
                % (env, sorted(set(_ALIASES))))
        _IMPL = _ALIASES[env]
        return _IMPL
    # queries the backend — only reached from execution paths (the
    # executor/tracer), never from graph construction
    import jax

    _IMPL = ("unsafe_rbg" if jax.devices()[0].platform == "tpu"
             else "threefry2x32")
    return _IMPL


def root_key(seed):
    """Typed root key of the platform-appropriate impl."""
    import jax

    return jax.random.key(int(seed), impl=_impl())


def key_data(key):
    """Typed key -> raw uint32 array (jit-boundary form: raw arrays
    device_put/shard like any other state; typed KeyArrays do not)."""
    import jax

    return jax.random.key_data(key)


def wrap_key_data(raw):
    """Raw uint32 array -> typed key of the platform impl (called INSIDE
    traced step functions)."""
    import jax

    return jax.random.wrap_key_data(raw, impl=_impl())
