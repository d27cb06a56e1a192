"""The cell ``glm-4.7-flash.train-s8192``'s own yardstick, at the
rehearsal's toy size on the CPU: the model's own faults (the MTP term left
out of the loss; nothing rotated; the routed experts' sum left out), the
two every training cell has (half of the batch left out - here half of
the one row's positions; the state left unchanged) and the fp8 control,
each in the reference put in the program's place, fail the rehearsal's
limits; the costs the cell's roofline divides by; and the reader this
cell brings (``mtp_share_pct.train``) on a synthetic trace - it returns
``None``, never 0, where there is nothing to read. The rehearsal of the
cell itself is ``test_rehearsal.py``'s, which runs every cell of
``BENCHMARK.json``. Not tier-1: ``python -m pytest benchmark/tests -q``."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run as harness  # noqa: E402

CELL = "glm-4.7-flash.train-s8192"
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
        assert {"mtp_0_eh_proj_w", "mtp_0_mla_q_b_w",
                "mtp_0_moe_gate_w"} <= set(ref["grad"])


def _faulty(cell, sound, fault):
    cfg, _, _, family, _ = cell
    assert fault in family.FAULTS
    for seed, (pool, ref) in sound.items():
        faulty = compare.reference_steps(
            family.reference_loss(cfg, compare.matmul("f32"), fault=fault),
            family.init_params(cfg, seed), pool, family.optimizer(cfg))
        yield seed, compare.gaps(faulty, ref)


@pytest.mark.parametrize("fault", ["no_mtp", "no_experts"])
def test_models_own_fault_fails_a_limit(cell, sound, fault):
    for seed, (num, _) in _faulty(cell, sound, fault):
        ok, rows = compare.judge(num, cell[2])
        assert not ok, (seed, rows)


def test_nothing_rotated_moves_latent_attentions_leaves_most(cell, sound):
    """``no_rope`` at the toy's 256 positions and 8 rotary channels: its
    worst leaves are latent attention's, a few times a sound run's worst,
    and the toy's limits catch it on some seeds only (the cell's own
    readings are ``PERF.md`` section 2's)."""
    for seed, (num, where) in _faulty(cell, sound, "no_rope"):
        assert "_mla_" in where["grad_gap"], (seed, where)
        assert "_mla_" in where["delta_gap"], (seed, where)
        assert num["grad_gap"] > 0.01 and num["delta_gap"] > 0.005, num


def test_half_of_the_batch_left_out_fails_a_limit(cell, sound):
    cfg, mix, limits, family, driver = cell
    for seed, (pool, ref) in sound.items():
        half = driver.reference(family, cfg, seed, pool,
                                half_batch=family.half_batch)
        ok, rows = compare.judge(compare.gaps(half, ref)[0], limits)
        assert not ok, (seed, rows)


def test_state_left_unchanged_reads_one_and_fails(cell, sound):
    for seed, (_, ref) in sound.items():
        still = dict(ref, delta={k: 0.0 for k in ref["delta"]})
        num, _ = compare.gaps(still, ref)
        assert num["delta_gap"] == pytest.approx(1.0)
        ok, rows = compare.judge(num, cell[2])
        assert not ok, (seed, rows)


def test_control_fp8_fails_a_limit(cell, sound):
    cfg, mix, limits, family, driver = cell
    for seed, (pool, ref) in sound.items():
        ctl = driver.reference(family, cfg, seed, pool, "fp8")
        ok, rows = compare.judge(compare.gaps(ctl, ref)[0], limits)
        assert not ok, (seed, rows)


def test_costs_count_six_latent_attention_layers_at_256():
    _, _, cfg, mix, _, family, _ = harness.load_cell(CELL)
    pairs, H, d = 8192 * 8193 // 2, 20, 256
    flops, nbytes = family.attention_cost(cfg, mix)
    assert flops == 6 * pairs * H * (2 * 2 * d + 2 * 3 * d + 2 * 4 * d)
    assert nbytes == 6 * 8192 * H * 8 * d * 2
    assert family.expected_kernel_tiers(cfg, mix) == ("flash", "flash_bwd")


# -- the reader on a synthetic trace -----------------------------------------
OP_SECONDS = {"fusion.1": 0.30, "fusion.2": 0.20, "attn_flash_fwd.3": 0.10,
              "fusion.4": 0.40}


def _run(trace=True, ops=OP_SECONDS):
    return {"trace": {"op_seconds": ops, "busy_s": 2.0} if trace else None}


def test_mtp_share_reads_the_modules_instructions(monkeypatch):
    from paddle_tpu.fluid import profiler

    reduce = harness.load_module("layer_metrics",
                                 "mtp_share_pct.train").reduce
    table = {"fusion.1": "mtp", "fusion.2": "", "attn_flash_fwd.3": "mtp",
             "fusion.4": ""}
    monkeypatch.setattr(profiler, "newest_step_modules", lambda: table)
    assert reduce(_run()) == pytest.approx(100 * 0.40 / 2.0)
    assert reduce(_run(trace=False)) is None
    # a step without the module: nothing to read
    monkeypatch.setattr(profiler, "newest_step_modules",
                        lambda: {k: "" for k in table})
    assert reduce(_run()) is None
    monkeypatch.setattr(profiler, "newest_step_modules", lambda: None)
    assert reduce(_run()) is None
    # a program from before the table
    monkeypatch.delattr(profiler, "newest_step_modules")
    assert reduce(_run()) is None
