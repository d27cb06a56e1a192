"""The cell ``keye-vl-2.0-30b-a3b.train-s16384``'s own yardstick, at the
rehearsal's toy size on the CPU: the three faults of this model's own (the
selection ignored; half of ``topk``; the routed experts' sum left out), the
two every training cell has (half of the batch left out - here half of the
one row's positions; the state left unchanged) and the fp8 control, each in
the reference put in the program's place, fail the rehearsal's limits; and
the three readers this cell brings (``index_share_pct`` /
``index_roofline`` / ``attn_select_roofline``) on a synthetic trace - each
returns ``None``, never 0, where there is nothing to read. The rehearsal
of the cell itself is ``test_rehearsal.py``'s, which runs every cell of
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

CELL = "keye-vl-2.0-30b-a3b.train-s16384"
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
        assert not set(ref["grad"]) & set(family.index_shapes(cfg))


@pytest.mark.parametrize("fault", ["dense", "topk_half", "no_experts"])
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
    assert feed["tokens"].shape[0] == 1
    assert (half["tokens"][:, s // 2:] == feed["tokens"][:, :s // 2]).all()
    assert (half["labels"][:, :s // 2] == feed["labels"][:, :s // 2]).all()
    assert (half["positions"] == feed["positions"]).all()
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


def test_costs_count_the_kept_pairs_and_the_causal_ones():
    _, _, cfg, mix, _, family, _ = harness.load_cell(CELL)
    L, d, H = 4, 128, 32
    flops, nbytes = family.attention_cost(cfg, mix)
    assert flops == L * 3.5 * 4 * d * H * 31458304
    flops, nbytes = family.index_cost(cfg, mix)
    assert flops == L * 2 * 64 * 16 * 134225920
    assert nbytes == L * (16384 * (1024 + 64 + 16) * 2 + 16384 ** 2)
    assert family.tokens_per_step(cfg, mix) == 16384
    assert family.expected_kernel_tiers(cfg, mix) == ("select",)
    # the model's work, never the masked pairs: attention over the kept
    # pairs is 3 x 4 d H kept a layer of it
    assert family.flops(cfg, mix) > 3 * L * 4 * d * H * 31458304
    assert family.flops(cfg, mix) < 3 * L * 4 * d * H * 134225920


# -- the readers on a synthetic trace ----------------------------------------
REGIONS = {
    "fusion.1": ("forward", "sparse_index"),
    "while.2": ("backward", "sparse_index"),
    "attn_select_fwd.3": ("forward", "fused_multihead_attention"),
    "fusion.6": ("forward", "mul"),
    "copy.7": ("unattributed", ""),
}
OP_SECONDS = {"fusion.1": 0.10, "while.2": 0.30, "attn_select_fwd.3": 0.20,
              "attn_select_bwd_dq.4": 0.25, "attn_select_bwd_dkv.5": 0.35,
              "attn_flash_fwd.9": 0.7, "fusion.6": 0.40, "copy.7": 0.05}


def _run(trace=True, peaks=True):
    family = types.SimpleNamespace(
        index_cost=lambda cfg, mix: (1e12, 1e9),        # 5.08 ms at the peak
        attention_cost=lambda cfg, mix: (8e12, 1e9))    # 40.6 ms
    return {"trace": {"op_seconds": OP_SECONDS, "busy_s": 2.0,
                      "op_calls": {k: 4 for k in OP_SECONDS}}
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
            for n in ("index_share_pct.train", "index_roofline.train",
                      "attn_select_roofline.train")}


def test_readers_read_the_ops_scope_and_the_kernels_names(readers):
    run = _run()
    assert readers["index_share_pct.train"](run) == pytest.approx(
        100 * 0.40 / 2.0)
    assert readers["index_roofline.train"](run) == pytest.approx(
        100 * (1e12 / 197e12) * 4 / 0.40)
    assert readers["attn_select_roofline.train"](run) == pytest.approx(
        100 * (8e12 / 197e12) * 4 / 0.80)       # not the flash kernel's 0.7


def test_readers_return_none_where_there_is_nothing_to_read(readers,
                                                            monkeypatch):
    from paddle_tpu.fluid import profiler

    for name, reduce in readers.items():
        assert reduce(_run(trace=False)) is None, name      # no trace
    for name in ("index_roofline.train", "attn_select_roofline.train"):
        assert readers[name](_run(peaks=False)) is None
    # a step with neither the op nor the kernels (the other cells)
    run = _run()
    run["trace"]["op_seconds"] = {"fusion.6": 0.4, "attn_flash_fwd.9": 0.7}
    for table in ({"fusion.6": ("forward", "mul")}, {}, None):
        monkeypatch.setattr(profiler, "newest_step_regions", lambda: table)
        for name, reduce in readers.items():
            assert reduce(run) is None, (name, table)
    # a program from before the table (the parent commit)
    monkeypatch.delattr(profiler, "newest_step_regions")
    for name in ("index_share_pct.train", "index_roofline.train"):
        assert readers[name](_run()) is None, name
    # a family without the cost functions
    monkeypatch.setattr(profiler, "newest_step_regions", lambda: REGIONS,
                        raising=False)
    run = _run()
    run["family"] = types.SimpleNamespace()
    for name in ("index_roofline.train", "attn_select_roofline.train"):
        assert readers[name](run) is None
