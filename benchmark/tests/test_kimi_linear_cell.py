"""The cell ``kimi-linear-48b-a3b.train-s16384``'s own yardstick, at the
rehearsal's toy size on the CPU: the three faults of this model's own (the
gate a head instead of a channel; a softmax router without the scaling
factor; the routed experts' sum left out), the two every training cell has
(half of the batch left out - here half of the one row's positions; the
state left unchanged) and the fp8 control, each in the reference put in the
program's place, fail the rehearsal's limits; the costs the cell's
rooflines divide by; and the reader this cell brings
(``attn_latent_roofline.train``) on a synthetic trace - it returns ``None``,
never 0, where there is nothing to read. The rehearsal of the cell itself
is ``test_rehearsal.py``'s, which runs every cell of ``BENCHMARK.json``.
Not tier-1: ``python -m pytest benchmark/tests -q``."""

import os
import sys
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run as harness  # noqa: E402

CELL = "kimi-linear-48b-a3b.train-s16384"
SEEDS = (5, 2147483777, 3000000011)


@pytest.fixture(scope="module")
def cell():
    _, _, cfg, mix, limits, family, driver = harness.load_cell(CELL)
    cfg, mix = family.tiny(cfg, mix)
    return cfg, mix, limits["rehearse"], family, driver


@pytest.fixture(scope="module")
def sound(cell):
    cfg, mix, _, family, driver = cell
    out = {}
    for seed in SEEDS:
        pool = family.feeds(cfg, mix, seed, compare.STEPS)
        out[seed] = (pool, driver.reference(family, cfg, seed, pool))
    return out


def test_sound_reference_against_itself_is_correct(cell, sound):
    for _, ref in sound.values():
        assert compare.judge(compare.gaps(ref, ref)[0], cell[2])[0]


def test_the_comparisons_leaves_are_the_trainable_ones(cell, sound):
    cfg, _, _, family, _ = cell
    for _, ref in sound.values():
        assert set(ref["grad"]) == set(family.param_shapes(cfg))
        assert not [k for k in ref["grad"] if k.endswith("router_bias")]


def test_the_tiny_preset_keeps_all_five_layer_kinds(cell):
    cfg, mix, _, family, _ = cell
    kinds = {k.split("_", 2)[2].split("_")[0]
             for k in family.param_shapes(cfg) if k.startswith("layer_")}
    assert {"kda", "mla", "mlp", "moe"} <= kinds
    assert mix["seq_len"] == 256 and mix["batch"] == 1
    assert family.expected_kernel_tiers(cfg, mix) == ("flash", "flash_bwd")


@pytest.mark.parametrize("fault", ["scalar_gate", "softmax_router",
                                   "no_experts"])
def test_models_own_fault_fails_a_limit(cell, sound, fault):
    cfg, mix, limits, family, _ = cell
    assert fault in family.FAULTS
    for seed, (pool, ref) in sound.items():
        faulty = compare.reference_steps(
            family.reference_loss(cfg, compare.matmul("f32"), fault=fault),
            family.init_params(cfg, seed), pool, family.optimizer(cfg))
        ok, rows = compare.judge(compare.gaps(faulty, ref)[0], limits)
        assert not ok, (seed, rows)


def test_half_of_the_batch_left_out_fails_a_limit(cell, sound):
    cfg, mix, limits, family, driver = cell
    for seed, (pool, ref) in sound.items():
        half = driver.reference(family, cfg, seed, pool,
                                half_batch=family.half_batch)
        ok, rows = compare.judge(compare.gaps(half, ref)[0], limits)
        assert not ok, (seed, rows)


def test_half_batch_of_one_row_is_half_of_its_positions(cell):
    cfg, mix, _, family, _ = cell
    feed = family.feeds(cfg, mix, 5, 1)[0]
    half = family.half_batch(feed)
    s = feed["tokens"].shape[1]
    assert feed["tokens"].shape[0] == 1 and set(feed) == {"tokens", "labels"}
    assert (half["tokens"][:, s // 2:] == feed["tokens"][:, :s // 2]).all()
    assert (half["labels"][:, :s // 2] == feed["labels"][:, :s // 2]).all()
    assert (half["tokens"] != feed["tokens"]).any()


def test_state_left_unchanged_reads_one_and_fails(cell, sound):
    for seed, (_, ref) in sound.items():
        still = dict(ref, delta={k: 0.0 for k in ref["delta"]})
        num, _ = compare.gaps(still, ref)
        assert num["delta_gap"] == pytest.approx(1.0)
        assert num["delta_mid"] > 0.9   # leaves under the median norm read less
        ok, rows = compare.judge(num, cell[2])
        assert not ok, (seed, rows)


def test_control_fp8_fails_a_limit(cell, sound):
    cfg, mix, limits, family, driver = cell
    for seed, (pool, ref) in sound.items():
        ctl = driver.reference(family, cfg, seed, pool, "fp8")
        ok, rows = compare.judge(compare.gaps(ctl, ref)[0], limits)
        assert not ok, (seed, rows)


def test_costs_count_the_causal_pairs_at_two_widths_and_the_chunked_rule():
    _, _, cfg, mix, _, family, _ = harness.load_cell(CELL)
    pairs, H = 134225920, 32
    flops, nbytes = family.attention_cost(cfg, mix)
    assert flops == pairs * H * (2 * (192 + 128) + 2 * (192 + 128 + 192)
                                 + 2 * (192 + 128 + 128 + 192))
    assert nbytes == 16384 * H * (4 * 192 + 4 * 128) * 2
    flops, nbytes = family.gdn_cost(cfg, mix)
    C, d, layers = 64, 128, 4
    assert flops == layers * 3 * (16384 // C) * H * 2 * C * (
        5 * C * d + 3 * d * d)
    assert nbytes == layers * 2 * 16384 * H * (4 * d * 2 + d * 4 + 4)
    assert family.tokens_per_step(cfg, mix) == 16384
    assert family.expected_kernel_tiers(cfg, mix) == ("flash", "flash_bwd")
    # 602.4 M trainable parameters, 16 B each
    import numpy as np

    n = sum(int(np.prod(s)) for s in family.param_shapes(cfg).values())
    assert n == 602433408
    # the model's work: over three times the parameters' 2 FLOPs a token
    # (embedding rows are looked up, experts held count 8 / 256 x top-8)
    assert 4.0e13 < family.flops(cfg, mix) < 4.4e13


# -- the reader on a synthetic trace -----------------------------------------
OP_SECONDS = {"attn_flash_fwd.3": 0.10, "attn_flash_bwd_dq.4": 0.12,
              "attn_flash_bwd_dkv.5": 0.18, "kda_chunk_fwd.1": 0.5,
              "fusion.6": 0.40}


def _run(trace=True, peaks=True, ops=OP_SECONDS):
    family = types.SimpleNamespace(
        attention_cost=lambda cfg, mix: (8e12, 1e9))    # 40.6 ms at the peak
    return {"trace": {"op_seconds": ops, "busy_s": 2.0,
                      "op_calls": {k: 4 for k in ops}} if trace else None,
            "steps": 4, "family": family, "cfg": {}, "mix": {},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}
            if peaks else None}


def test_latent_roofline_reads_the_attention_kernels_by_name():
    reduce = harness.load_module("layer_metrics",
                                 "attn_latent_roofline.train").reduce
    assert reduce(_run()) == pytest.approx(
        100 * (8e12 / 197e12) * 4 / 0.40)       # not the delta rule's 0.5
    assert reduce(_run(trace=False)) is None
    assert reduce(_run(peaks=False)) is None
    # a step without attention kernels, or with a forward and no backward
    assert reduce(_run(ops={"fusion.6": 0.4, "kda_chunk_fwd.1": 0.5})) is None
    assert reduce(_run(ops={"attn_flash_fwd.3": 0.1})) is None
    run = _run()
    run["family"] = types.SimpleNamespace()     # no cost function
    assert reduce(run) is None
