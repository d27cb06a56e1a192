"""The cell ``qwen3-next-80b-a3b.train-s8192``'s own yardstick, at the
rehearsal's toy size on the CPU: the two faults of this model's own
(routed experts' sum left out; the DeltaNet's decay forced to 0), the two
every training cell has (half of the batch left out; the state left
unchanged) and the fp8 control, each in the reference put in the program's
place, fail the rehearsal's limits (``test_faults.py`` plants the last two
under the program's own step, for this cell too); and the three readers this cell brings (``gdn_share_pct``
/ ``moe_share_pct`` / ``gdn_roofline``) on a synthetic trace - each returns
``None``, never 0, where there is nothing to read. The rehearsal of the
cell itself is ``test_rehearsal.py``'s, which runs every cell of
``BENCHMARK.json``. Not tier-1: ``python -m pytest benchmark/tests -q``."""

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

CELL = "qwen3-next-80b-a3b.train-s8192"
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


@pytest.mark.parametrize("fault", ["no_routed_experts", "g_zero"])
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


# -- the readers on a synthetic trace ----------------------------------------
REGIONS = {
    "fusion.1": ("forward", "gated_delta_rule"),
    "fusion.2": ("backward", "gated_delta_rule"),
    "fusion.3": ("forward", "causal_conv1d"),
    "ragged-dot.4": ("forward", "moe_experts"),
    "fusion.5": ("backward", "moe_route"),
    "fusion.6": ("forward", "mul"),
    "copy.7": ("unattributed", ""),
}
OP_SECONDS = {"fusion.1": 0.10, "fusion.2": 0.30, "fusion.3": 0.05,
              "ragged-dot.4": 0.08, "fusion.5": 0.02, "fusion.6": 0.40,
              "copy.7": 0.05, "not_in_the_table.8": 0.5}


def _run(trace=True, peaks=True):
    family = types.SimpleNamespace(
        gdn_cost=lambda cfg, mix: (2e12, 1e9))     # 10.15 ms at the peak
    return {"trace": {"op_seconds": OP_SECONDS, "busy_s": 1.5}
            if trace else None,
            "steps": 4, "family": family, "cfg": {}, "mix": {},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}
            if peaks else None}


@pytest.fixture
def readers(monkeypatch):
    from paddle_tpu.fluid import profiler

    monkeypatch.setattr(profiler, "newest_step_regions", lambda: REGIONS,
                        raising=False)
    return {n: harness.load_module("layer_metrics", n).reduce
            for n in ("gdn_share_pct.train", "moe_share_pct.train",
                      "gdn_roofline.train")}


def test_readers_file_device_time_under_program_ops(readers):
    run = _run()
    assert readers["gdn_share_pct.train"](run) == pytest.approx(
        100 * 0.45 / 1.5)
    assert readers["moe_share_pct.train"](run) == pytest.approx(
        100 * 0.10 / 1.5)
    least = 2e12 / 197e12       # compute-bound: 1e9 / 819e9 is less
    assert readers["gdn_roofline.train"](run) == pytest.approx(
        100 * least * 4 / 0.40)


def test_readers_return_none_where_there_is_nothing_to_read(readers,
                                                            monkeypatch):
    from paddle_tpu.fluid import profiler

    for name, reduce in readers.items():
        assert reduce(_run(trace=False)) is None, name      # no trace
    assert readers["gdn_roofline.train"](_run(peaks=False)) is None
    # a step with no such op (the BERT cells), and no step at all
    for table in ({"fusion.6": ("forward", "mul")}, {}, None):
        monkeypatch.setattr(profiler, "newest_step_regions", lambda: table)
        for name, reduce in readers.items():
            assert reduce(_run()) is None, (name, table)
    # a program from before the table (the parent commit)
    monkeypatch.delattr(profiler, "newest_step_regions")
    for name, reduce in readers.items():
        assert reduce(_run()) is None, name
    # a family without the cost function
    monkeypatch.setattr(profiler, "newest_step_regions", lambda: REGIONS,
                        raising=False)
    run = _run()
    run["family"] = types.SimpleNamespace()
    assert readers["gdn_roofline.train"](run) is None
