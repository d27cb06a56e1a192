"""``run.py --rehearse-cpu`` for every cell of ``BENCHMARK.json``: the last
line has the contract's keys and ``correct`` is true; off the chip and
without the flag the command refuses and prints no result. Each cell is a
process of its own, as the driver runs it. Not tier-1: run by hand,
``python -m pytest benchmark/tests -q``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(cell, trace, *flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    env.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:] + [
            "--workload", cell, "--seed", "2147483659", "--seconds", "1",
            "--trace", str(trace)] + list(flags),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def reported(cell, section):
    return {m["name"] for m in BENCH[section]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_line(cell):
    p = run_cell(cell, 0, "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == reported(cell, "end_to_end")
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu" and line["rehearsal"]
    for name, row in line["compared"].items():
        assert row["value"] <= row["limit"], (name, row)
    # the numbers compared are the last lines of standard error too
    tail = p.stderr.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert tail[-1] == "correct True"
    assert all(t.startswith("compared ") for t in tail[:-1])


def test_traced_rehearsal_reports_only_what_a_cpu_can():
    cell = CELLS[0]
    p = run_cell(cell, 1, "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    by_source = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    assert line["metrics"], line
    assert set(line["metrics"]) <= reported(cell, "per_layer")
    assert not [n for n in line["metrics"]
                if by_source[n] == "device_trace"], line["metrics"]
    assert "mfu.train" not in line["metrics"]       # no peak for a CPU
    assert line["metrics"]["compiles_in_window.train"]["value"] == 0


def test_refuses_off_the_chip():
    p = run_cell(CELLS[0], 0)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refusing to run" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import peaks

    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
