"""The two decoder models at their rehearsal sizes with recomputation at
the layer boundaries, attention on the Pallas interpreter: the compiled
step names each forward attention kernel once a layer that has one (its
``o`` and row logsumexp cross the boundary,
``kernels.common.keep_across_recompute``, as does the expert layer's
dispatch plan), and recomputation changes no loss."""

import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the model's cell, its forward kernel's tier, the layers that run it (of
# 4), what its segments keep
_MODELS = {
    "keye": ("keye-vl-2.0-30b-a3b.train-s16384", "attn_select", 4,
             ("attn_select", "sparse_index", "moe_plan", "moe_route")),
    "qwen": ("qwen3-next-80b-a3b.train-s8192", "attn_flash", 1,
             ("attn_flash", "moe_plan", "moe_route")),
}


def _rehearsal(cell):
    """``(family, cfg, mix)`` of the cell's CPU rehearsal preset."""
    import run as harness

    _, _, cfg, mix, _, family, _ = harness.load_cell(cell)
    return (family,) + family.tiny(cfg, mix)


def _three_losses(fam, cfg, mix):
    import compare

    step = fam.build(cfg, mix)
    step.set_params(fam.init_params(cfg, 11))
    return [float(np.asarray(step.run(f)).ravel()[0])
            for f in fam.feeds(cfg, mix, 11, compare.STEPS)]


@pytest.mark.parametrize("model", sorted(_MODELS))
def test_a_recomputed_step_runs_each_forward_attention_kernel_once(
        monkeypatch, model):
    from paddle_tpu.fluid import monitor, profiler
    from paddle_tpu.kernels import attention as A

    cell, tier, attention_layers, kept = _MODELS[model]
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    # S = 192 (qwen) reaches the flash tier at tile 64
    monkeypatch.setattr(A, "_FLASH_BLOCK_CANDIDATES", (64,))
    monkeypatch.setattr(A, "_MAX_FUSED_SEQ", 64)
    monkeypatch.setattr(A, "_MAX_LONG_SEQ", 0)
    fam, cfg, mix = _rehearsal(cell)
    assert mix["recompute"] and cfg["num_hidden_layers"] == 4

    def kept_bytes():
        return {w: monitor.counter("recompute_kept_bytes_total",
                                   labels={"what": w}).value for w in kept}

    before = kept_bytes()
    losses = _three_losses(fam, cfg, mix)
    assert all(n > before[w] for w, n in kept_bytes().items())
    fn, specs = profiler._NEWEST_STEP
    names = profiler.op_names_of(fn.lower(*specs).compile().as_text())
    for kernel in ("_fwd", "_bwd_dq", "_bwd_dkv"):
        # a call site = the scope the kernel's instructions sit under
        mark = "/%s%s/" % (tier, kernel)
        sites = collections.Counter(n.split(mark)[0] for n in names.values()
                                    if mark in n)
        assert len(sites) == attention_layers, (kernel, sorted(sites))
    if model == "qwen":     # its first case with recomputation
        # (bf16 activations: XLA fuses the two programs differently, and
        # the second and third loss differ by 4e-6; bit-for-bit equality
        # with the bare checkpoint is tests/test_recompute.py's)
        without = _three_losses(fam, cfg, dict(mix, recompute=False))
        np.testing.assert_allclose(losses, without, rtol=1e-4)
