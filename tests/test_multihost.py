"""Multi-host SPMD runtime — the TCP coordination service
(distributed/coordination.py), the TcpRendezvous built on it, the
launcher's coord-port handling, and the hierarchical DCN
data-parallelism layer (c_hierarchical_allreduce /
HierarchicalGradAllReduce / parallel.cross_host), ending in a 2-process
fake cluster bootstrapped with no shared filesystem at all."""

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUNNER = os.path.join(REPO, "tests", "dist_runner_multihost.py")

from paddle_tpu.distributed import coordination, rendezvous, wire  # noqa: E402
from paddle_tpu.fluid import monitor  # noqa: E402


# -- wire framing (satellite: shared framed-TCP plumbing) --------------------

def test_wire_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        wire.send_all(a, wire.frame(b"hello" * 100))
        assert wire.read_frame(b) == b"hello" * 100
    finally:
        a.close()
        b.close()


def test_wire_frame_too_large_is_connection_error():
    a, b = socket.socketpair()
    try:
        wire.send_all(a, wire.frame(b"x" * 1000))
        with pytest.raises(wire.FrameTooLarge):
            wire.read_frame(b, max_bytes=100)
        assert issubclass(wire.FrameTooLarge, ConnectionError), \
            "an oversized frame leaves the stream unsyncable"
    finally:
        a.close()
        b.close()


def test_wire_peer_close_mid_frame():
    a, b = socket.socketpair()
    a.sendall(b"\x10\x00\x00\x00abc")  # 16-byte frame, 3 bytes sent
    a.close()
    try:
        with pytest.raises(ConnectionError):
            wire.read_frame(b)
    finally:
        b.close()


# -- coordination service ----------------------------------------------------

@pytest.fixture
def coord():
    srv = coordination.CoordServer().start()
    client = coordination.CoordClient(srv.endpoint)
    yield srv, client
    client.close()
    srv.stop()


def test_coord_kv_roundtrip(coord):
    _, c = coord
    assert c.get("missing") is None
    c.put("k", b"v1")
    assert c.get("k") == b"v1"
    c.put("k", "v2")  # str values encode transparently
    assert c.get("k") == b"v2"
    assert sorted(c.keys("")) == ["k"]
    assert c.delete("k") is True
    assert c.delete("k") is False  # atomic claim: second deleter loses
    assert c.get("k") is None


def test_coord_fetch_add_interops_with_get(coord):
    _, c = coord
    assert c.add("ctr", 1) == 1
    assert c.add("ctr", 2) == 3
    # the counter is stored as ascii so plain get() reads it too
    assert int(c.get("ctr")) == 3


def test_coord_wait_get_blocks_until_put(coord):
    srv, c = coord
    other = coordination.CoordClient(srv.endpoint)
    try:
        t = threading.Thread(
            target=lambda: (time.sleep(0.2), other.put("late", b"ok")))
        t.start()
        t0 = time.monotonic()
        assert c.get("late", wait=True, timeout=10.0) == b"ok"
        assert time.monotonic() - t0 < 9.0  # woke on the put, not timeout
        t.join()
    finally:
        other.close()


def test_coord_barrier_releases_at_world(coord):
    srv, _ = coord
    gens = []

    def member(cid):
        cl = coordination.CoordClient(srv.endpoint)
        try:
            gens.append(cl.barrier("step", world=2, client_id=cid,
                                   timeout=30.0))
        finally:
            cl.close()

    ts = [threading.Thread(target=member, args=("m%d" % i,))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert gens == [1, 1]


def test_coord_barrier_arrival_is_idempotent(coord):
    srv, c = coord
    # the same client id arriving twice must NOT release a world-2
    # barrier (transport retries would otherwise double-count)
    with pytest.raises(TimeoutError):
        c.barrier("dup", world=2, client_id="only", timeout=0.5)
    with pytest.raises(TimeoutError):
        c.barrier("dup", world=2, client_id="only", timeout=0.5)
    # "only" stays registered server-side; one DISTINCT id completes
    # the world-2 barrier immediately
    other = coordination.CoordClient(srv.endpoint)
    try:
        assert other.barrier("dup", world=2, client_id="late",
                             timeout=30.0) == 1
    finally:
        other.close()


def test_coord_broadcast(coord):
    srv, c = coord
    got = []
    other = coordination.CoordClient(srv.endpoint)
    try:
        t = threading.Thread(
            target=lambda: got.append(other.broadcast("blob",
                                                      timeout=30.0)))
        t.start()
        assert c.broadcast("blob", value=b"payload") == b"payload"
        t.join(timeout=60)
        assert got == [b"payload"]
    finally:
        other.close()


def test_coord_lease_liveness(coord):
    _, c = coord
    c.lease("w0", ttl=30.0)
    c.lease("w1", ttl=0.2)
    assert "w0" in c.live() and "w1" in c.live()
    time.sleep(0.4)
    live = c.live()
    assert "w0" in live and "w1" not in live  # expired lease pruned


def test_coord_wrong_token_rejected():
    srv = coordination.CoordServer(token="sesame").start()
    try:
        # the handshake happens at connect time, so construction raises
        with pytest.raises((ConnectionError, RuntimeError)):
            coordination.CoordClient(srv.endpoint, token="wrong").ping()
        ok = coordination.CoordClient(srv.endpoint, token="sesame")
        try:
            ok.ping()
        finally:
            ok.close()
    finally:
        srv.stop()


def test_coord_malformed_payload_keeps_server_alive(coord):
    _, c = coord
    with pytest.raises(RuntimeError):
        # opcode PUT with a truncated key header -> typed decode error
        # frame, NOT a dropped connection
        c._conn.request(b"\x01\xff")
    c.put("still", b"alive")
    assert c.get("still") == b"alive"


def test_coord_metrics_registered(coord):
    _, c = coord
    c.put("m", b"1")
    c.get("m")
    dump = monitor.dump_json()
    for name in ("coord_puts_total", "coord_gets_total",
                 "coord_barriers_total", "coord_barrier_wait_seconds",
                 "coord_watch_clients"):
        assert name in dump, name
    assert dump["coord_puts_total"][0]["value"] >= 1
    assert dump["coord_gets_total"][0]["value"] >= 1


# -- TcpRendezvous (satellite: file backend stays, TCP added) ----------------

@pytest.fixture
def tcp_rdzv():
    srv = coordination.CoordServer().start()
    r = rendezvous.TcpRendezvous(addr=srv.endpoint)
    yield r
    r.close()
    srv.stop()


def test_tcp_rendezvous_world_roundtrip(tcp_rdzv):
    assert tcp_rdzv.world() is None
    tcp_rdzv.record_world(2, generation=3)
    w = tcp_rdzv.world()
    assert w["world_size"] == 2
    assert w["slots"] == [0, 1]
    assert tcp_rdzv.generation() == 3


def test_tcp_rendezvous_slot_claim_is_atomic(tcp_rdzv):
    tcp_rdzv.offer_slot(1)
    tcp_rdzv.offer_slot(2)
    assert sorted(tcp_rdzv.returned_slots()) == [1, 2]
    assert sorted(tcp_rdzv.consume_slots()) == [1, 2]
    assert tcp_rdzv.consume_slots() == []  # second consumer gets nothing


def test_tcp_rendezvous_members(tcp_rdzv, monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    tcp_rdzv.announce(rank=0, step=5)
    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    tcp_rdzv.announce(rank=1, step=5)
    members = tcp_rdzv.members()
    assert sorted(members) == [0, 1]
    assert members[1]["step"] == 5
    tcp_rdzv.clear_members()
    assert tcp_rdzv.members() == {}


def test_rendezvous_create_backend_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv(coordination.ENV_BACKEND, raising=False)
    monkeypatch.delenv(coordination.ENV_ADDR, raising=False)
    r = rendezvous.create(backend="file", dirname=str(tmp_path))
    assert isinstance(r, rendezvous.Rendezvous)
    srv = coordination.CoordServer().start()
    try:
        r = rendezvous.create(backend="tcp", addr=srv.endpoint)
        assert isinstance(r, rendezvous.TcpRendezvous)
        r.close()
        # env-driven: PADDLE_COORD_BACKEND/ADDR select TCP
        monkeypatch.setenv(coordination.ENV_BACKEND, "tcp")
        monkeypatch.setenv(coordination.ENV_ADDR, srv.endpoint)
        r = rendezvous.create()
        assert isinstance(r, rendezvous.TcpRendezvous)
        r.close()
    finally:
        srv.stop()
    with pytest.raises(ValueError):
        rendezvous.create(backend="carrier-pigeon")


# -- launcher coord-port handling (satellite: port-range regression) ---------

def test_coord_server_bind_race_picks_fresh_base(monkeypatch):
    """A lost bind race on the coordination port retries with a FRESH
    base, counting launch_port_retries_total but never the restart
    budget (the server starts before any worker spawn)."""
    from paddle_tpu.distributed import launch as L

    nproc = 2
    blocker = socket.socket()  # bind-only blocker forcing the collision
    blocker.bind(("127.0.0.1", 0))
    taken = blocker.getsockname()[1]
    good = wire.reserve_port_range(nproc + 1)
    bases = [taken - nproc, good]  # first base -> coord port collides
    monkeypatch.setattr(
        L, "_reserve_port_range",
        lambda n, tries=10, extra=0: bases.pop(0))
    retries_before = L._M_PORT_RETRIES.value
    restarts_before = L._M_RESTARTS.value
    try:
        srv, base = L._start_coord_server("127.0.0.1", nproc,
                                          started_port=None, port_retries=3)
    finally:
        blocker.close()
    try:
        assert base == good
        c = coordination.CoordClient(srv.endpoint)
        c.ping()
        c.close()
    finally:
        srv.stop()
    assert L._M_PORT_RETRIES.value == retries_before + 1
    assert L._M_RESTARTS.value == restarts_before  # budget untouched


def test_coord_server_explicit_port_does_not_retry(monkeypatch):
    """--started_port pins the range: a bind failure there must raise,
    not silently migrate the gang to other ports."""
    from paddle_tpu.distributed import launch as L

    blocker = socket.socket()  # bind-only port blocker for the test
    blocker.bind(("127.0.0.1", 0))
    taken = blocker.getsockname()[1]
    try:
        with pytest.raises(OSError):
            L._start_coord_server("127.0.0.1", 2, started_port=taken - 2,
                                  port_retries=5)
    finally:
        blocker.close()


# -- hierarchical collectives ------------------------------------------------

def _build_mlp(seed=7):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, optimizer

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=8, act="relu",
                      param_attr=fluid.ParamAttr(name="hh_w1"))
        p = layers.fc(h, size=1, param_attr=fluid.ParamAttr(name="hh_w2"))
        loss = layers.mean(layers.square(p - y))
        optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _train(transpiler, steps=5, **compile_kw):
    import paddle_tpu.fluid as fluid

    rng = np.random.default_rng(0)
    feed = {"x": rng.normal(size=(16, 4)).astype(np.float32),
            "y": rng.normal(size=(16, 1)).astype(np.float32)}
    main, startup, loss = _build_mlp()
    transpiler.transpile(startup, main)
    compiled = fluid.CompiledProgram(main).with_explicit_collectives(
        loss_name=loss.name, **compile_kw)
    exe = fluid.Executor()
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(steps):
            (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
        w = np.asarray(exe.run(compiled, feed=feed, fetch_list=["hh_w1"])[0])
    return losses, w


def test_hierarchical_transpiler_matches_flat():
    from paddle_tpu.fluid.transpiler.collective import (
        GradAllReduce, HierarchicalGradAllReduce)

    flat_l, flat_w = _train(GradAllReduce(nranks=8))
    hier_l, hier_w = _train(HierarchicalGradAllReduce(nranks=8),
                            mesh_axes=("host", "device"),
                            mesh_shape={"host": 2, "device": 4})
    np.testing.assert_allclose(hier_l, flat_l, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(hier_w, flat_w, rtol=1e-5, atol=1e-6)


def test_hierarchical_transpiler_op_mix():
    from paddle_tpu.fluid.transpiler.collective import (
        HierarchicalGradAllReduce)

    main, startup, _ = _build_mlp()
    HierarchicalGradAllReduce(nranks=8).transpile(startup, main)
    types = [op.type for op in main.global_block().ops]
    assert types.count("c_hierarchical_allreduce") == 4  # w1/b1/w2/b2
    assert "c_allreduce_sum" not in types


def test_hierarchical_dgc_splits_rings():
    """Under DGC the DENSE grad reduces in-host (ring 1 = ICI) and only
    the compressed output crosses hosts (ring 0 = DCN)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, optimizer
    from paddle_tpu.fluid.transpiler.collective import (
        HierarchicalGradAllReduce)

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        p = layers.fc(x, size=1, param_attr=fluid.ParamAttr(name="dg_w"))
        loss = layers.mean(p)
        optimizer.DGCMomentumOptimizer(0.1, 0.9,
                                       sparsity=(0.75,)).minimize(loss)
    HierarchicalGradAllReduce(nranks=8).transpile(startup, main)
    ops = main.global_block().ops
    dgc_ops = [o for o in ops if o.type == "dgc"]
    assert dgc_ops, "DGC optimizer must emit dgc ops"
    dense = set()
    for o in dgc_ops:
        dense.update(o.input("Grad"))
    compressed = set()
    for o in dgc_ops:
        compressed.update(o.output("GradOut"))
    ici = [o for o in ops if o.type == "c_allreduce_sum"
           and o.attr("ring_id", 0) == 1]
    dcn = [o for o in ops if o.type == "c_allreduce_sum"
           and o.attr("ring_id", 0) == 0]
    assert {n for o in ici for n in o.input("X")} == dense
    assert {n for o in dcn for n in o.input("X")} == compressed
    assert not any(o.type == "c_hierarchical_allreduce"
                   and set(o.input("X")) & dense for o in ops)


def test_hier_psum_matches_flat_psum():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel import hier_psum, make_host_device_mesh

    mesh = make_host_device_mesh(2, 4)
    x = np.arange(8 * 5, dtype=np.float32).reshape(8, 5) * 0.25

    def hier(v):
        return hier_psum(v)

    def flat(v):
        return jax.lax.psum(v, ("host", "device"))

    kw = dict(mesh=mesh, in_specs=P(("host", "device")), out_specs=P(),
              check_vma=False)
    got = jax.shard_map(hier, **kw)(jnp.asarray(x))
    want = jax.shard_map(flat, **kw)(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)


def test_feed_sharding_spans_both_mesh_axes():
    import paddle_tpu.fluid as fluid

    main, _, loss = _build_mlp()
    compiled = fluid.CompiledProgram(main).with_explicit_collectives(
        loss_name=loss.name, mesh_axes=("host", "device"),
        mesh_shape={"host": 2, "device": 4})
    sh = compiled.feed_sharding(np.zeros((16, 3), np.float32))
    assert sh.spec[0] == ("host", "device")
    # batch only divisible by the host axis: leading-axis fallback
    sh = compiled.feed_sharding(np.zeros((4, 3), np.float32))
    assert sh.spec[0] == "host"
    # batch divisible by neither: replicated
    sh = compiled.feed_sharding(np.zeros((3, 3), np.float32))
    assert not any(sh.spec)


# -- CrossHostGradSync -------------------------------------------------------

def test_crosshost_allreduce_matches_flat_mean():
    from paddle_tpu.parallel import CrossHostGradSync

    rng = np.random.default_rng(1)
    grads = [rng.normal(size=(2, 4, 3, 5)).astype(np.float32),
             rng.normal(size=(2, 4, 7)).astype(np.float32)]
    sync = CrossHostGradSync(hosts=2, devices_per_host=4)
    out = sync.allreduce(grads)
    for g, o in zip(grads, out):
        want = np.broadcast_to(g.mean(axis=(0, 1), keepdims=True), g.shape)
        np.testing.assert_allclose(np.asarray(o), want, rtol=1e-5,
                                   atol=1e-6)


def test_crosshost_allreduce_local_is_per_host():
    from paddle_tpu.parallel import CrossHostGradSync

    rng = np.random.default_rng(2)
    g = rng.normal(size=(2, 4, 6)).astype(np.float32)
    sync = CrossHostGradSync(hosts=2, devices_per_host=4)
    (o,) = sync.allreduce_local([g])
    want = np.broadcast_to(g.mean(axis=1, keepdims=True), g.shape)
    np.testing.assert_allclose(np.asarray(o), want, rtol=1e-5, atol=1e-6)


def test_crosshost_dgc_compresses_dcn_only():
    from paddle_tpu.parallel import CrossHostGradSync

    monitor.reset()
    rng = np.random.default_rng(3)
    g = rng.normal(size=(2, 4, 64)).astype(np.float32)
    sync = CrossHostGradSync(hosts=2, devices_per_host=4, dgc_ratio=0.25)
    (o1,) = sync.allreduce([g])
    (o2,) = sync.allreduce([g])  # residuals carry across steps
    assert np.isfinite(np.asarray(o1)).all()
    assert np.isfinite(np.asarray(o2)).all()
    dump = monitor.dump_json()
    by_phase = {e["labels"]["phase"]: e
                for e in dump["crosshost_allreduce_bytes_total"]}
    # DCN bytes are ratio-scaled; ICI stays dense
    assert by_phase["dcn"]["value"] < by_phase["ici"]["value"]


def test_crosshost_localsgd_sync_cadence():
    from paddle_tpu.parallel import CrossHostGradSync

    rng = np.random.default_rng(4)
    p = rng.normal(size=(2, 4, 5)).astype(np.float32)
    sync = CrossHostGradSync(hosts=2, devices_per_host=4,
                             local_sgd_steps=3)
    params = [p]
    assert sync.localsgd_params(params, step=0) is params  # off-step
    assert sync.localsgd_params(params, step=1) is params
    (o,) = sync.localsgd_params(params, step=2)  # (2+1) % 3 == 0
    want = np.broadcast_to(p.mean(axis=0, keepdims=True), p.shape)
    np.testing.assert_allclose(np.asarray(o), want, rtol=1e-5, atol=1e-6)


def test_crosshost_metrics_label_phases():
    from paddle_tpu.parallel import CrossHostGradSync

    monitor.reset()
    g = np.ones((2, 2, 8), np.float32)
    CrossHostGradSync(hosts=2, devices_per_host=2).allreduce([g])
    dump = monitor.dump_json()
    for name in ("crosshost_allreduce_seconds",
                 "crosshost_allreduce_bytes_total"):
        phases = {e["labels"]["phase"] for e in dump[name]}
        assert phases == {"ici", "dcn"}, (name, phases)


# -- end-to-end: 2 hosts x 2 devices over pure TCP ---------------------------

def _hier_baseline():
    """Single-process 2x2 hierarchical run over 4 of the local devices
    — the same mesh shape the 2-process gang builds globally."""
    from paddle_tpu.fluid.transpiler.collective import (
        HierarchicalGradAllReduce)
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, optimizer
    import jax

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 23
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[8], dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        h = layers.fc(x, size=16, act="relu",
                      param_attr=fluid.ParamAttr(name="mh_w1"))
        logits = layers.fc(h, size=4,
                           param_attr=fluid.ParamAttr(name="mh_w2"))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        optimizer.SGD(0.1).minimize(loss)
    HierarchicalGradAllReduce(nranks=4).transpile(startup, main)
    compiled = fluid.CompiledProgram(main).with_explicit_collectives(
        loss_name=loss.name, places=jax.devices()[:4],
        mesh_axes=("host", "device"),
        mesh_shape={"host": 2, "device": 2})
    exe = fluid.Executor()
    rng = np.random.RandomState(1)
    feed = {"x": rng.rand(16, 8).astype(np.float32),
            "label": rng.randint(0, 4, (16, 1)).astype(np.int64)}
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(4):
            (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(lv).ravel()[0]))
    return losses


@pytest.mark.multihost
@pytest.mark.slow
def test_two_host_hierarchical_dp_matches_single_process(tmp_path):
    """2 processes x 2 devices, bootstrapped purely over the TCP
    coordination service (no PADDLE_RENDEZVOUS_DIR anywhere), must
    reproduce the single-process 4-device hierarchical run."""
    base = _hier_baseline()

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env.pop("PADDLE_RENDEZVOUS_DIR", None)
    log_dir = str(tmp_path / "logs")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--backend", "cpu",
           "--rendezvous_backend", "tcp", "--log_dir", log_dir, RUNNER]
    r = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                       timeout=600)
    logs = ""
    for i in range(2):
        with open(os.path.join(log_dir, "worker.%d.log" % i)) as f:
            logs += "--- worker %d ---\n%s\n" % (i, f.read())
    assert r.returncode == 0, logs

    per_rank = re.findall(r"LOSSES (\[.*\])", logs)
    assert len(per_rank) == 2, logs
    l0, l1 = json.loads(per_rank[0]), json.loads(per_rank[1])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)  # same global loss
    np.testing.assert_allclose(l0, base, rtol=1e-4)
    digests = re.findall(r"WDIGEST (\S+)", logs)
    assert len(digests) == 2, logs
    assert float(digests[0]) == float(digests[1])  # replicated params
