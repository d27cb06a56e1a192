"""The comparison has been shown to fail: a run of the harness on the CPU
(the look for a chip skipped by ``--rehearse-cpu``) with the timed path
broken underneath comes out not correct, once for each fault a training
cell can have, and so does a reference with a layer left out; and the
control, the reference with fp8 matmuls put in the program's place, fails
the cell's own limits at a size a test run can hold. In one process, so
the programs are built once a family."""

import json
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
import fluid_step  # noqa: E402
import run as harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
# one cell a configuration: the faults are the family's
CELLS = list({w["config"]: w["name"] for w in BENCH["workloads"]}.values())


def rehearse(cell, capsys):
    harness.main(["--workload", cell, "--seed", "3000000007", "--seconds",
                  "0.3", "--trace", "0", "--rehearse-cpu"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failed(line):
    return [n for n, row in line["compared"].items()
            if not row["value"] <= row["limit"]]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    line = rehearse(cell, capsys)
    assert line["correct"] is True and not failed(line)


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(cell, capsys, monkeypatch):
    import jax.numpy as jnp

    sound = fluid_step.FluidStep.run

    def run(self, feed):
        keep = {n: jnp.copy(self.scope.find_var(n))
                for n in self.scope.var_names()}
        loss = sound(self, feed)
        for n, v in keep.items():
            self.scope.set_var(n, v)
        return loss

    monkeypatch.setattr(fluid_step.FluidStep, "run", run)
    line = rehearse(cell, capsys)
    assert line["correct"] is False
    assert "delta_gap" in failed(line)
    assert line["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(cell, capsys, monkeypatch):
    sound = fluid_step.FluidStep.run
    family = harness.load_cell(cell)[5]

    def run(self, feed):
        return sound(self, family.half_batch(feed))

    monkeypatch.setattr(fluid_step.FluidStep, "run", run)
    line = rehearse(cell, capsys)
    assert line["correct"] is False and failed(line)


@pytest.mark.parametrize("cell", CELLS)
def test_reference_with_a_layer_left_out(cell, capsys, monkeypatch):
    sound = compare.reference_steps

    def skipping(loss_fn, params, feeds, opt, half_batch=None):
        def short(p, feed):
            return loss_fn({k: v[:-1] if k.startswith("layers.") else v
                            for k, v in p.items()}, feed)

        return sound(short, params, feeds, opt, half_batch)

    monkeypatch.setattr(compare, "reference_steps", skipping)
    line = rehearse(cell, capsys)
    assert line["correct"] is False and failed(line)


# -- the control, at a size a test run can hold ------------------------------
MID = {
    "bert_pretrain": (
        dict(vocab_size=4096, hidden_size=256, num_hidden_layers=4,
             num_attention_heads=4, intermediate_size=1024,
             max_position_embeddings=128),
        dict(batch=8, seq_len=128, predictions_per_row=19)),
    "transformer_nmt": (
        dict(vocab_size=4096, d_model=256, h=4, d_ff=1024, N=3, max_len=64),
        dict(batch=8, src_len=64, tgt_len=64)),
}


@pytest.mark.parametrize("cell", CELLS)
def test_control_fp8_fails_the_cells_limits(cell):
    _, _, cfg, mix, limits, family, driver = harness.load_cell(cell)
    cfg.update(MID[cfg["family"]][0])
    mix.update(MID[cfg["family"]][1])
    for seed in (1, 2147483777, 3000000011):
        pool = family.feeds(cfg, mix, seed, compare.STEPS)
        ref = driver.reference(family, cfg, seed, pool)
        ctl = driver.reference(family, cfg, seed, pool, "fp8")
        num, _ = compare.gaps(ctl, ref)
        ok, rows = compare.judge(num, limits)
        assert not ok, (seed, rows)
        same, _ = compare.gaps(ref, ref)
        assert compare.judge(same, limits)[0]
