"""chip_smoke.py off the chip: it must refuse, before compiling anything,
and the compile-cache helper it calls must put JAX's cache at one fixed
place unless the environment has placed it already. What it proves ON
the chip is in CHANGES.md; nothing here runs at real size."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, **env_changes):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable] + args, env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)


def test_refuses_off_tpu_and_cache_dir_is_fixed():
    r = _run([SMOKE], JAX_PLATFORMS="cpu", PADDLE_TPU_PALLAS_INTERPRET="1",
             JAX_COMPILATION_CACHE_DIR=None)
    assert r.returncode != 0
    assert "platform=cpu" in r.stderr
    assert "PADDLE_TPU_PALLAS_INTERPRET=1 is set" in r.stderr
    assert '"ok"' not in r.stdout and '"phase"' not in r.stdout
    # from the file's location: not from tempfile, a pid or a clock
    want = os.path.join(ROOT, ".jax_cache")
    assert "compilation cache: %s\n" % want in r.stdout


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path):
    import jax

    from paddle_tpu.fluid import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.use_jax_cache() == before
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.slow
def test_rehearsal_runs_every_phase():
    import chip_smoke       # conftest put the checkout on sys.path

    r = _run([SMOKE, "--rehearse-cpu"], PADDLE_TPU_PALLAS_INTERPRET=None)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "platform=cpu rehearsal" in r.stdout
    for phase in chip_smoke.PHASES:
        assert '{"phase": "%s"' % phase in r.stdout
    assert '"ok"' not in r.stdout
