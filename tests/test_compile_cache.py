"""Persistent compile cache: AOT-serialized executables on disk
(fluid/compile_cache.py) — restart hits, corruption quarantine, version
mismatch, cross-process races, prelowered models, LRU eviction."""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import inference
from paddle_tpu.fluid import compile_cache, layers, monitor, unique_name

pytestmark = pytest.mark.compile_cache


def _build_regression():
    """The canonical tiny train program; unique_name.guard makes repeat
    builds byte-identical (like a fresh process would be)."""
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(x, 1, name="cc_fc")
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=8, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, 4).astype(np.float32),
            "y": rng.rand(batch, 1).astype(np.float32)}


def _run_restart(feed, steps=2):
    """One simulated process lifetime: fresh Executor (empty memory
    tier), fresh program build, `steps` training steps."""
    main, startup, loss = _build_regression()
    exe = fluid.Executor()
    out = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(steps):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            out.append(float(np.asarray(lv)))
    return out


def _counters():
    return (monitor.counter("executor_compile_cache_disk_hit_total").value,
            monitor.counter("executor_compile_cache_disk_miss_total").value,
            monitor.counter("compile_cache_quarantined_total").value)


def _entries(d):
    return sorted(f for f in os.listdir(d)
                  if f.endswith(compile_cache.ENTRY_SUFFIX))


def test_disabled_is_inert(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    h0, m0, _ = _counters()
    losses = _run_restart(_feed())
    assert np.isfinite(losses).all()
    h1, m1, _ = _counters()
    assert (h1, m1) == (h0, m0), "disk tier consulted while disabled"


def test_restart_hits_disk_and_is_bit_identical(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    h0, m0, _ = _counters()
    cold = _run_restart(_feed())
    h1, m1, _ = _counters()
    assert m1 - m0 == 2, "cold run: startup + main should both miss disk"
    assert h1 == h0
    assert len(_entries(str(tmp_path))) == 2
    # "restart": fresh Executor + rebuilt program, same cache dir
    warm = _run_restart(_feed())
    h2, m2, _ = _counters()
    assert warm == cold, "deserialized executable diverged from live"
    assert h2 - h1 == 2 and m2 == m1, \
        "warm restart should compile zero programs live"
    # tier-labeled view moved with the unlabeled counters
    disk_hits = monitor.counter("executor_compile_cache_hit_total",
                                labels={"tier": "disk"}).value
    assert disk_hits >= 2


def test_corrupted_entry_quarantined_never_fatal(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    cold = _run_restart(_feed())
    paths = _entries(str(tmp_path))
    # truncate one entry, garbage-overwrite the other
    with open(os.path.join(str(tmp_path), paths[0]), "r+b") as f:
        f.truncate(17)
    with open(os.path.join(str(tmp_path), paths[1]), "wb") as f:
        f.write(b"\x80\x04 not a cache entry")
    _, m0, q0 = _counters()
    warm = _run_restart(_feed())
    _, m1, q1 = _counters()
    assert warm == cold, "fallback live compile diverged"
    assert q1 - q0 == 2, "both bad entries should be quarantined"
    assert m1 - m0 == 2, "bad entries must count as disk misses"
    # quarantined aside (evidence kept), fresh entries re-saved
    quarantined = [f for f in os.listdir(str(tmp_path))
                   if f.endswith(compile_cache.QUARANTINE_SUFFIX)]
    assert len(quarantined) == 2
    assert len(_entries(str(tmp_path))) == 2


def test_version_bump_misses_cleanly(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    _run_restart(_feed())
    before = _entries(str(tmp_path))
    # a jax/jaxlib upgrade changes the env fingerprint -> different key
    monkeypatch.setattr(compile_cache, "FORMAT_VERSION",
                        compile_cache.FORMAT_VERSION + 1)
    h0, m0, q0 = _counters()
    _run_restart(_feed())
    h1, m1, q1 = _counters()
    assert h1 == h0, "stale-version entry must not load"
    assert m1 - m0 == 2
    assert q1 == q0, "a clean version miss is not a quarantine"
    after = _entries(str(tmp_path))
    assert set(before) < set(after) and len(after) == 4


def test_two_processes_race_same_dir(tmp_path):
    """Two fresh processes populating one cache dir concurrently: both
    succeed (atomic rename, no torn reads) and the dir converges."""
    script = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PADDLE_COMPILE_CACHE_DIR"] = sys.argv[1]
sys.path.insert(0, sys.argv[2])
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="float32")
    pred = layers.fc(x, 1, name="cc_fc")
    loss = layers.reduce_mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
exe = fluid.Executor()
rng = np.random.RandomState(0)
feed = {"x": rng.rand(8, 4).astype(np.float32),
        "y": rng.rand(8, 1).astype(np.float32)}
with fluid.scope_guard(fluid.Scope()):
    exe.run(startup)
    (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
print("LOSS=%.9f" % float(np.asarray(lv)))
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_DIR}
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(tmp_path), repo],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True) for _ in range(2)]
    outs = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err
    losses = {o.strip() for o, _ in outs}
    assert len(losses) == 1, "racing processes diverged: %r" % losses
    assert len(_entries(str(tmp_path))) == 2


def test_prelowered_model_cold_start(tmp_path, monkeypatch):
    """save_inference_model(prelower=True) -> a Predictor in a process
    with NO cache dir configured cold-starts from the model-adjacent
    executables, compiling zero programs live."""
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        pred = layers.fc(x, 3, name="pl_fc", act="softmax")
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(
            model_dir, ["x"], [pred], exe, main_program=main,
            prelower=True, prelower_batch_sizes=(1, 4))
    pl_dir = os.path.join(model_dir, compile_cache.PRELOWERED_DIRNAME)
    assert len(_entries(pl_dir)) == 2
    h0, m0, _ = _counters()
    p = inference.Predictor(inference.Config(model_dir=model_dir))
    out4 = p.run({"x": np.ones((4, 4), np.float32)})
    h1, m1, _ = _counters()
    assert h1 - h0 == 1 and m1 == m0, "prelowered batch=4 should hit"
    assert np.allclose(np.sum(out4[0], axis=1), 1.0, atol=1e-5)
    # a batch size outside the prelowered set compiles live, and with
    # no write dir configured it must NOT write into the model dir
    p.run({"x": np.ones((2, 4), np.float32)})
    h2, m2, _ = _counters()
    assert h2 == h1 and m2 - m1 == 1
    assert len(_entries(pl_dir)) == 2


def test_cold_serve_values_match(tmp_path):
    """A COLD process serving through deserialized prelowered
    executables must return the same values as the live program.

    Regression: inference executables used to be serialized with state
    donation baked in; the deserialized copies then ran in-place over
    param buffers, so a cold Server returned stale or garbage rows
    (the in-process path hides this — only a fresh process serves
    through the deserialized executables with nothing else resolved).
    """
    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        pred = layers.fc(x, 3, name="cs_fc", act="softmax")
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(
            model_dir, ["x"], [pred], exe, main_program=main,
            prelower=True, prelower_batch_sizes=(1, 2))
    # ground truth straight from the saved params — independent of any
    # executable, live or deserialized
    w = np.asarray(scope.vars["cs_fc.w_0"])
    b = np.asarray(scope.vars["cs_fc.b_0"])
    rng = np.random.RandomState(7)
    feeds = [rng.rand(rng.randint(1, 3), 4).astype(np.float32)
             for _ in range(8)]
    np.savez(str(tmp_path / "feeds.npz"),
             **{"f%d" % i: f for i, f in enumerate(feeds)})
    script = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("PADDLE_COMPILE_CACHE_DIR", None)
sys.path.insert(0, sys.argv[2])
import numpy as np
from paddle_tpu import inference
from paddle_tpu.fluid import monitor
d = np.load(os.path.join(sys.argv[1], "feeds.npz"))
feeds = [d["f%d" % i] for i in range(8)]
p = inference.Predictor(os.path.join(sys.argv[1], "model"))
srv = inference.Server()
srv.register("m", p, inference.ServeConfig(max_batch_size=2,
                                           max_queue_delay_ms=1.0),
             warmup_feed={"x": np.zeros((1, 4), np.float32)})
outs = [srv.submit("m", {"x": f}).result(timeout=60)[0] for f in feeds]
srv.close()
np.savez(os.path.join(sys.argv[1], "outs.npz"),
         **{"o%d" % i: o for i, o in enumerate(outs)})
print("MISS=%d" % monitor.counter(
    "executor_compile_cache_disk_miss_total").value)
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_DIR}
    r = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path), repo],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "MISS=0" in r.stdout, \
        "cold serve compiled live instead of deserializing: %s" % r.stdout
    got = np.load(str(tmp_path / "outs.npz"))
    for i, f in enumerate(feeds):
        z = f @ w + b
        e = np.exp(z - z.max(axis=1, keepdims=True))
        ref = e / e.sum(axis=1, keepdims=True)
        np.testing.assert_allclose(
            got["o%d" % i], ref, rtol=1e-4, atol=1e-5,
            err_msg="cold-served request %d diverged from the saved "
                    "params' forward pass" % i)


def test_lru_eviction_by_mtime(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    _run_restart(_feed())
    entries = _entries(str(tmp_path))
    assert len(entries) == 2
    sizes = {f: os.path.getsize(os.path.join(str(tmp_path), f))
             for f in entries}
    # age one entry far into the past, then set a budget that only fits
    # the other: the old one must go
    newest = max(entries, key=lambda f: os.path.getmtime(
        os.path.join(str(tmp_path), f)))
    oldest = [f for f in entries if f != newest][0]
    old_path = os.path.join(str(tmp_path), oldest)
    os.utime(old_path, (1, 1))
    monkeypatch.setenv(compile_cache.ENV_MAX_BYTES,
                       str(sizes[newest] + 16))
    e0 = monitor.counter("compile_cache_evicted_total").value
    evicted = compile_cache._evict(str(tmp_path))
    assert evicted == 1
    assert _entries(str(tmp_path)) == [newest]
    assert monitor.counter("compile_cache_evicted_total").value - e0 == 1


def test_prewarm_validates_and_quarantines(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    _run_restart(_feed())
    bad = os.path.join(str(tmp_path), "0" * 64 + compile_cache.ENTRY_SUFFIX)
    with open(bad, "wb") as f:
        f.write(b"torn write")
    _, _, q0 = _counters()
    ok = compile_cache.prewarm(str(tmp_path))
    _, _, q1 = _counters()
    assert ok == 2
    assert q1 - q0 == 1
    assert not os.path.exists(bad)
    # the quarantined bytes are kept aside for postmortem
    assert os.path.exists(bad + compile_cache.QUARANTINE_SUFFIX)


def test_restore_on_restart_prewarms(tmp_path, monkeypatch):
    """A launcher-restarted worker (PADDLE_RESTART_ATTEMPT>0) validates
    the cache before its first step: the corrupt entry is quarantined
    by restore_on_restart itself, not discovered mid-step."""
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    bad = os.path.join(str(tmp_path), "f" * 64 + compile_cache.ENTRY_SUFFIX)
    with open(bad, "wb") as f:
        f.write(b"garbage")
    monkeypatch.setenv("PADDLE_RESTART_ATTEMPT", "1")
    monkeypatch.setenv("PADDLE_CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    mgr = fluid.io.CheckpointManager(str(tmp_path / "ckpt"))
    _, _, q0 = _counters()
    assert mgr.restore_on_restart() is None  # no checkpoint yet
    _, _, q1 = _counters()
    assert q1 - q0 == 1 and not os.path.exists(bad)


def test_one_device_entry_reloads_on_multi_device_host(tmp_path,
                                                       monkeypatch):
    """An executable compiled for ONE of the 8 devices reloads for that
    device — not for every device of the backend, which loads and then
    dies at the first call wanting 8 shards. And an entry that does
    load but cannot run is quarantined at that call, not fatal."""
    import pickle

    import jax
    import jax.numpy as jnp

    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(4.0), dev)

    def restart():
        return compile_cache.wrap_jit(jax.jit(lambda v: v * 2), "pin")(x)

    h0, m0, q0 = _counters()
    cold = restart()
    warm = restart()
    h1, m1, q1 = _counters()
    assert (h1 - h0, m1 - m0, q1 - q0) == (1, 1, 0)
    assert warm.devices() == {dev}
    assert np.array_equal(np.asarray(warm), np.asarray(cold))

    path = compile_cache.entry_path(str(tmp_path), "pin")
    with open(path, "rb") as f:
        entry = pickle.load(f)  # bytes this test's own cold run wrote
    entry["devices"] = [0]      # loads fine, cannot take x on device 3
    with open(path, "wb") as f:
        pickle.dump(entry, f)
    again = restart()
    h2, m2, q2 = _counters()
    assert (h2 - h1, m2 - m1, q2 - q1) == (0, 1, 1)
    assert np.array_equal(np.asarray(again), np.asarray(cold))
