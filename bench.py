"""Benchmark entry: prints ONE JSON line with the headline metric.

Runs on a TPU only: the default ``python bench.py`` exits non-zero on any
other platform (``--smoke`` is the CPU leg, and times nothing). Flagship
benchmark: BERT-base MLM pretraining train-step throughput (BASELINE.json
`configs` entry 3 — the reference's ERNIE/BERT Fleet workload), tokens/sec
on one chip. ``vs_baseline`` is null: the reference publishes no benchmark
figures (BASELINE.json `published`).

Auditability (the reference's profiler table / op_tester discipline,
``/root/reference/paddle/fluid/platform/profiler.h:166``):
  * step_time_ms and analytic model FLOPs/step are reported alongside
    tokens/sec, and MFU = achieved FLOP/s / chip peak bf16 FLOP/s.
  * the measurement is validated by doubling iters and requiring stable
    tokens/sec (catches un-timed async work), and by a "checked" pass that
    fetches the loss every step and requires it to be finite and decreasing.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets; jax
# exposes one device per chip, so these are per-chip figures).
_PEAK_BF16 = {
    "tpu v2": 45e12,
    "tpu v3": 123e12,
    "tpu v4": 275e12,
    "tpu v5 lite": 197e12,
    "tpu v5e": 197e12,
    "tpu v5": 459e12,
    "tpu v5p": 459e12,
    "tpu v6 lite": 918e12,
    "tpu v6e": 918e12,
    "tpu v6": 918e12,
}


def _peak_flops(device):
    """Peak bf16 FLOP/s for the detected chip. Overridable via
    BENCH_PEAK_FLOPS; a device kind that is not in the table is an
    error, not a default."""
    env = os.environ.get("BENCH_PEAK_FLOPS")
    if env:
        return float(env), "env:BENCH_PEAK_FLOPS"
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key in sorted(_PEAK_BF16, key=len, reverse=True):
        if key in kind:
            return _PEAK_BF16[key], "device_kind:%s" % kind
    raise ValueError(
        "no peak bf16 FLOP/s known for device_kind %r; the table holds %r "
        "— add the chip or set BENCH_PEAK_FLOPS" % (kind, _PEAK_BF16))


def _refuse_children_on_held_chip(leg):
    """A chip belongs to one process. By the time an opt-in leg runs,
    ``bench_bert`` has initialised the backend in this process, so the
    replica subprocesses ``leg`` starts could not have the chip: they
    would fail, hang, or serve from whatever platform JAX falls to.
    Refuse instead of timing that."""
    import jax

    if jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            "%s starts replica subprocesses, and this process already "
            "holds the tpu; run it from a parent that stays off JAX, "
            "one FleetSupervisor (env= naming the chip) per chip" % leg)


def bert_train_flops_per_step(cfg, batch, seq, n_pred=None):
    """Analytic matmul FLOPs for one BERT MLM training step (fwd+bwd ~= 3x
    fwd; 2*M*N*K per matmul). Embedding gathers and elementwise ignored.
    The MLM head runs on the gathered masked positions (n_pred per
    sequence), like the reference's ERNIE mask_pos head — the vocab
    projection FLOPs scale with n_pred, not seq."""
    h, L, V = cfg.hidden, cfg.n_layers, cfg.vocab_size
    per_layer = 24 * batch * seq * h * h + 4 * batch * seq * seq * h
    rows = batch * (n_pred if n_pred else seq)
    head = 2 * rows * h * h + 2 * rows * h * V
    return 3 * (L * per_layer + head)


def _timed_run(exe, main, batch, loss, iters, jax, use_iters=False):
    if use_iters:
        # step-batched window (exe.run(..., iters=k)): ONE dispatch drives
        # all k steps device-side (lax.scan with donated state), so the
        # window measures compute, not k Python+PJRT round trips — this is
        # what stabilized the host-overhead-bound configs (LeNet swung
        # ±40% run-to-run, DeepFM lost 20% under host contention). The
        # feed is loop-invariant (per-step shape, reused each iteration);
        # the untimed first call compiles the k-step executable (k is part
        # of the compile-cache key). fetch_mode="async" keeps the loss
        # trajectory as a FetchHandle — run() issues no host sync, the
        # window closes on block_until_ready (device done, no transfer),
        # and the finiteness check syncs AFTER timing.
        (h,) = exe.run(main, feed=batch, fetch_list=[loss],
                       iters=iters, fetch_mode="async")
        h.block_until_ready()
        t0 = time.perf_counter()
        (h,) = exe.run(main, feed=batch, fetch_list=[loss],
                       iters=iters, fetch_mode="async")
        h.block_until_ready()
        elapsed = time.perf_counter() - t0
        assert np.isfinite(h.numpy()).all()
        return elapsed
    # drain in-flight work so the window times exactly `iters` steps —
    # with millisecond-scale steps any carried-over dispatch shows up as a
    # fixed cost that fakes better scaling at higher iters
    (lv,) = exe.run(main, feed=batch, fetch_list=[loss], return_numpy=False)
    jax.block_until_ready(lv)
    t0 = time.perf_counter()
    for _ in range(iters):
        # keep the loss as a device future: materializing a scalar every
        # step would serialize host and device (training loops fetch
        # metrics every N steps, not every step)
        (lv,) = exe.run(main, feed=batch, fetch_list=[loss],
                        return_numpy=False)
    jax.block_until_ready(lv)
    elapsed = time.perf_counter() - t0
    assert np.isfinite(np.asarray(lv)).all()
    return elapsed


def _stable_throughput(exe, main, feed, loss, iters, jax, units_per_step,
                       what, use_iters=False):
    """Measurement-validation protocol shared by every bench: time `iters`
    then `2*iters` steps; the rates must agree within [0.7, 1.43) or the
    harness is measuring less than it claims. Returns (rate at 2*iters,
    rate at iters, step seconds from the longer run). ``use_iters`` runs
    each window as one step-batched dispatch (``exe.run(..., iters=k)``)."""
    elapsed = _timed_run(exe, main, feed, loss, iters, jax, use_iters)
    elapsed2 = _timed_run(exe, main, feed, loss, 2 * iters, jax, use_iters)
    r1 = units_per_step * iters / elapsed
    r2 = units_per_step * 2 * iters / elapsed2
    assert 0.7 < r2 / r1 < 1.43, (
        "%s not stable when iters doubles (%.0f vs %.0f): the harness is "
        "measuring less than it claims" % (what, r1, r2))
    return r2, r1, elapsed2 / (2 * iters)


def _profile_table(exe, main, batch, loss, jax, steps=3,
                   out_path="bench_profile.txt"):
    """BENCH_PROFILE=1: trace `steps` steps with jax.profiler, parse the
    XPlane proto, and write a per-op device-time table (reference
    ``platform/profiler.h:166`` per-op tables). Parsing needs the
    xplane proto bundled with tensorflow; degrades to a notice when
    absent."""
    import glob as _glob
    import shutil
    import tempfile
    import collections
    import re as _re

    tracedir = tempfile.mkdtemp(prefix="bench_xplane_")
    try:
        jax.profiler.start_trace(tracedir)
        for _ in range(steps):
            (lv,) = exe.run(main, feed=batch, fetch_list=[loss])
        np.asarray(lv)
        jax.profiler.stop_trace()
        try:
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
        except Exception as e:  # pragma: no cover - env without TF
            with open(out_path, "w") as f:
                f.write("xplane parser unavailable (%s); raw trace kept "
                        "in %s\n" % (e, tracedir))
            return
        files = _glob.glob(tracedir + "/**/*.xplane.pb", recursive=True)
        if not files:
            with open(out_path, "w") as f:
                f.write("no .xplane.pb produced under %s\n" % tracedir)
            return
        xs = xplane_pb2.XSpace()
        with open(files[0], "rb") as f:
            xs.ParseFromString(f.read())
        planes = [p for p in xs.planes if "/device:" in p.name
                  and any(len(ln.events) for ln in p.lines)]
        lines = []
        for plane in planes:
            md = plane.event_metadata
            for ln in plane.lines:
                if ln.name != "XLA Ops":
                    continue
                per_inst = collections.Counter()
                per_family = collections.Counter()
                n_inst = collections.Counter()
                total = 0
                for ev in ln.events:
                    name = md[ev.metadata_id].name
                    inst = name.split(" = ")[0].strip().lstrip("%")
                    fam = _re.sub(r"\.\d+$", "", inst)
                    shape = name.split(" = ")[1].split(" ")[0] \
                        if " = " in name else ""
                    per_inst[(inst, shape)] += ev.duration_ps
                    per_family[fam] += ev.duration_ps
                    n_inst[fam] += 1
                    total += ev.duration_ps
                lines.append("== %s: %.3f ms/step device op time ==" %
                             (plane.name, total / 1e9 / steps))
                lines.append("-- by fusion family --")
                for fam, ps in per_family.most_common(15):
                    lines.append("%10.3f ms/step %5.1f%% n=%-5d %s" % (
                        ps / 1e9 / steps, 100.0 * ps / max(total, 1),
                        n_inst[fam] // steps, fam))
                lines.append("-- top instructions --")
                for (inst, shape), ps in per_inst.most_common(25):
                    lines.append("%10.3f ms/step %5.1f%%  %s  %s" % (
                        ps / 1e9 / steps, 100.0 * ps / max(total, 1),
                        inst, shape[:70]))
        if not lines:
            lines = ["no device plane with an 'XLA Ops' line in the "
                     "trace (CPU/interpret run?)"]
        with open(out_path, "w") as f:
            f.write("\n".join(lines) + "\n")
        print("profile table -> %s" % out_path, file=sys.stderr)
    finally:
        shutil.rmtree(tracedir, ignore_errors=True)


def bench_bert(batch_size=128, seq_len=128, warmup=8, iters=25):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    import jax

    cfg = bert.BertConfig.base()
    main, startup, loss = bert.build_pretrain_program(cfg, seq_len=seq_len,
                                                      use_amp=True)
    exe = fluid.Executor()
    batch = bert.synthetic_batch(cfg, batch_size, seq_len)
    # pre-stage the batch on device (the DataLoader double-buffer path does
    # this during training), so the window times steps, not transfers
    batch = {k: jax.device_put(v) for k, v in batch.items()}

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        # checked pass: loss must be finite every step and decrease overall
        losses = []
        for _ in range(max(warmup, 4)):  # doubles as compile warmup
            (lv,) = exe.run(main, feed=batch, fetch_list=[loss])
            l = float(np.asarray(lv).ravel()[0])
            assert np.isfinite(l), "non-finite loss in checked pass"
            losses.append(l)
        assert losses[-1] < losses[0], (
            "loss did not decrease in checked pass: %r" % losses)

        tps2, tps, step_s = _stable_throughput(
            exe, main, batch, loss, iters, jax, batch_size * seq_len,
            "bert tokens/sec")
        if os.environ.get("BENCH_PROFILE") == "1":
            _profile_table(exe, main, batch, loss, jax)

    # report the larger (more averaged) run
    step_time_ms = step_s * 1e3
    flops = bert_train_flops_per_step(cfg, batch_size, seq_len,
                                      bert.max_predictions(seq_len))
    dev = jax.devices()[0]
    peak, peak_source = _peak_flops(dev)
    achieved = flops / (step_time_ms / 1e3)
    mfu = achieved / peak
    return {
        "tokens_per_sec": round(tps2, 1),
        "tokens_per_sec_half_iters": round(tps, 1),
        "step_time_ms": round(step_time_ms, 3),
        "model_flops_per_step": flops,
        "achieved_flops_per_sec": round(achieved, 1),
        "peak_flops_per_sec": peak,
        "peak_source": peak_source,
        "mfu": round(mfu, 4),
        "batch_size": batch_size,
        "seq_len": seq_len,
        "loss_decreased": True,
    }


def resnet50_train_flops_per_step(batch, image_size=224):
    """Analytic: ResNet-50 fwd ≈ 4.1 GFLOP per 224² image; train ≈ 3x."""
    per_image = 4.1e9 * (image_size / 224.0) ** 2
    return 3 * batch * per_image


def bench_resnet(batch_size=256, image_size=224, warmup=3, iters=10):
    """BASELINE config 2 (ResNet-50 images/sec/chip); opt-in via
    BENCH_RESNET=1 so the driver's default bench stays one workload.
    Batch 256: the v5e sweep (r5) gives 2435 img/s vs 2373 at 128."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    import jax

    main, startup, loss, acc = resnet.build_train_program(
        image_size=image_size, use_amp=True)
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {
        "img": jax.device_put(rng.rand(
            batch_size, 3, image_size, image_size).astype("float32")),
        "label": jax.device_put(rng.randint(
            0, 1000, (batch_size, 1)).astype("int64")),
    }
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(warmup):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()
        ips, _, step_s = _stable_throughput(
            exe, main, feed, loss, iters, jax, batch_size,
            "resnet images/sec")
        if os.environ.get("BENCH_PROFILE") == "1":
            _profile_table(exe, main, feed, loss, jax,
                           out_path="bench_profile_resnet.txt")
    step_ms = step_s * 1e3
    flops = resnet50_train_flops_per_step(batch_size, image_size)
    peak, peak_source = _peak_flops(jax.devices()[0])
    mfu = flops / (step_ms / 1e3) / peak
    assert mfu <= 1.0, (
        "resnet MFU %.3f > 1: peak table wrong or timing missed work"
        % mfu)
    return {"resnet50_images_per_sec": round(ips, 1),
            "resnet50_step_time_ms": round(step_ms, 3),
            "resnet50_mfu": round(mfu, 4),
            "resnet50_peak_source": peak_source,
            "resnet50_batch_size": batch_size}


def bench_lenet(batch_size=1024, warmup=10, iters=100):
    """BASELINE config 1 (MNIST LeNet images/sec/chip, the first e2e
    milestone); opt-in via BENCH_LENET=1. Steps are host-overhead bound
    (the device step is a few ms), so the timed windows run step-batched
    (exe.run(..., iters=k): one dispatch, k device-side steps) and
    measure compute."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import lenet

    import jax

    main, startup, loss, acc = lenet.build_train_program()
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {"img": jax.device_put(
                rng.rand(batch_size, 1, 28, 28).astype("float32")),
            "label": jax.device_put(
                rng.randint(0, 10, (batch_size, 1)).astype("int64"))}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(warmup):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()
        ips, _, step_s = _stable_throughput(
            exe, main, feed, loss, iters, jax, batch_size,
            "lenet images/sec", use_iters=True)
    return {"lenet_images_per_sec": round(ips, 1),
            "lenet_step_time_ms": round(step_s * 1e3, 3),
            "lenet_batch_size": batch_size}


def bench_longseq(batch_size=8, seq_len=2048, warmup=3, iters=10,
                  prefix="longseq"):
    """Long-context single-chip BERT (opt-in BENCH_LONGSEQ=1): s=2048
    exercises the Q-tiled long kernels (dispatch tier 2), s=4096 the
    flash split-backward tier (kernels/attention.py)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert

    import jax

    cfg = bert.BertConfig.base()  # fresh instance per call
    cfg.max_seq = seq_len
    main, startup, loss = bert.build_pretrain_program(cfg, seq_len=seq_len,
                                                      use_amp=True)
    exe = fluid.Executor()
    batch = {k: jax.device_put(v)
             for k, v in bert.synthetic_batch(cfg, batch_size,
                                              seq_len).items()}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(warmup):
            (lv,) = exe.run(main, feed=batch, fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()
        tps, _, step_s = _stable_throughput(
            exe, main, batch, loss, iters, jax, batch_size * seq_len,
            prefix + " tokens/sec")
    flops = bert_train_flops_per_step(cfg, batch_size, seq_len,
                                      bert.max_predictions(seq_len))
    peak, peak_source = _peak_flops(jax.devices()[0])
    mfu = flops / step_s / peak
    assert mfu <= 1.0, (
        "%s MFU %.3f > 1: peak table wrong or timing missed work"
        % (prefix, mfu))
    return {prefix + "_tokens_per_sec": round(tps, 1),
            prefix + "_step_time_ms": round(step_s * 1e3, 3),
            prefix + "_mfu": round(mfu, 4),
            prefix + "_peak_source": peak_source,
            prefix + "_batch_size": batch_size,
            prefix + "_seq_len": seq_len}


def bench_longctx(shard_counts=(1, 2, 4, 8), budget_mb=64, warmup=2,
                  iters=5):
    """Sequence-parallel long-context tier (opt-in BENCH_LONGCTX=1):
    ring/Ulysses attention over the 'sp' mesh axis
    (kernels/attention.py sequence_parallel_attention).

    Four measurements back the tier's claims:
    1. max trainable S under a fixed per-device activation budget, per
       shard count — per-device ring memory is O(S/n) (each device holds
       its q chunk plus one rotating KV chunk), so max S must rise
       STRICTLY with the shard count (asserted). Sized with the static
       liveness estimator (utils/liveness.py) over the fwd+bwd jaxpr of
       one device's chunk-vs-chunk attention step.
    2. attention tokens/sec at fixed global S over 1->8 shards (actual
       shard_map dispatch; on CPU forwarding the virtual devices share
       cores, so the curve is layout overhead, not speedup — on a real
       ICI ring it is the scaling curve).
    3. recompute (RecomputeOptimizer over the transformer's per-block
       checkpoint vars): peak live bytes with vs without at fixed S —
       must drop — with the loss trajectory unchanged (asserted).
    4. sequence-sharded decode: seq_shards=4 session vs unsharded —
       token streams must be identical (asserted).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph, layers, optimizer
    from paddle_tpu.kernels.attention import sequence_parallel_attention
    from paddle_tpu.models import transformer
    from paddle_tpu.utils import liveness

    H, D, B = 4, 64, 1
    budget = budget_mb * 2 ** 20
    out = {"longctx_budget_mb": budget_mb}

    # -- 1. max trainable S per shard count (liveness-sized) ------------
    def chunk_peak_bytes(s_local):
        """fwd+bwd peak of ONE device's per-hop chunk attention — the
        memory that actually bounds S on a fixed-HBM device."""
        q = jnp.zeros((B, s_local, H * D), jnp.float32)

        def step(q, k, v):
            o = sequence_parallel_attention(q, k, v, H, mesh=None,
                                            causal=True)
            return jnp.sum(o * o)

        closed = jax.make_jaxpr(jax.grad(step, argnums=(0, 1, 2)))(q, q, q)
        return liveness.peak_live_bytes(closed)

    max_s = {}
    for n in shard_counts:
        s = 256
        while chunk_peak_bytes(2 * s // n) <= budget and s < 2 ** 20:
            s *= 2
        max_s[n] = s
        out["longctx_max_trainable_s_%dshard" % n] = s
    ordered = [max_s[n] for n in sorted(shard_counts)]
    assert all(a < b for a, b in zip(ordered, ordered[1:])), (
        "max trainable S not strictly increasing with shard count: %r"
        % max_s)

    # -- 2. tokens/sec at fixed global S over the shard ladder ----------
    S_fix = 2048
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S_fix, H * D).astype(np.float32) * 0.5)
    for n in shard_counts:
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n),
                    ("dp", "sp"))

        def step(q, k, v, mesh=mesh, n=n):
            o = sequence_parallel_attention(
                q, k, v, H, mesh=mesh if n > 1 else None, causal=True,
                strategy="ring" if n > 1 else "auto")
            return jnp.sum(o * o)

        g = jax.jit(jax.grad(step, argnums=(0, 1, 2)))
        for _ in range(warmup):
            jax.block_until_ready(g(q, q, q))
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(g(q, q, q))
        dt = (time.perf_counter() - t0) / iters
        out["longctx_attn_tokens_per_sec_%dshard" % n] = \
            round(B * S_fix / dt, 1)
    out["longctx_attn_seq_len"] = S_fix

    # -- 3. recompute: lower peak, unchanged losses ---------------------
    V, Bm, Sm = 64, 4, 64

    def trace_tiny():
        with dygraph.guard():
            model = transformer.Transformer(
                V, V, d_model=32, n_heads=4, d_inner=64, n_layers=2,
                max_len=Sm, dropout_rate=0.0, seq_parallel=True,
                attn_strategy="ring")
            prng = np.random.RandomState(7)
            for _, p in model.named_parameters():
                p.set_value(prng.uniform(-0.1, 0.1,
                                         p.shape).astype(np.float32))
            src, tgt, labels, pos = transformer.synthetic_batch(
                V, V, Bm, Sm)
            bias = transformer.make_causal_bias(Sm)
            args = [dygraph.to_variable(x)
                    for x in (src, tgt, pos, pos, bias)]
            _, tl = dygraph.jit.trace(model, args)
        return model, tl, (src, tgt, pos, bias, labels)

    def train(model, tl, data, recompute):
        src, tgt, pos, bias, labels = data
        startup = fluid.Program()
        with fluid.program_guard(tl.program, startup):
            logits = tl.program.global_block().var(tl._fetch_names[0])
            label = layers.data("lc_label", [Sm, 1], dtype="int64")
            ce = layers.softmax_with_cross_entropy(
                layers.reshape(logits, [-1, V]),
                layers.reshape(label, [-1, 1]))
            loss = layers.mean(ce)
            opt = optimizer.SGD(learning_rate=0.1)
            if recompute:
                opt = optimizer.RecomputeOptimizer(opt)
                opt._set_checkpoints(model.checkpoint_vars(tl.program))
            opt.minimize(loss)
        tl._materialize_scope()
        exe = fluid.Executor()
        feed = dict(zip(tl._feed_names, (src, tgt, pos, pos, bias)))
        feed["lc_label"] = labels
        losses = []
        with fluid.scope_guard(tl._scope):
            exe.run(startup)
            for _ in range(3):
                (lv,) = exe.run(tl.program, feed=feed, fetch_list=[loss])
                losses.append(float(np.asarray(lv)))
        return losses, tl, feed, loss.name

    m0, tl0, data = trace_tiny()
    base, tl0, feed0, l0 = train(m0, tl0, data, False)
    m1, tl1, _ = trace_tiny()
    rec, tl1, feed1, l1 = train(m1, tl1, data, True)
    assert max(abs(a - b) for a, b in zip(base, rec)) < 1e-5, (
        "recompute changed the loss trajectory: %r vs %r" % (base, rec))
    p0 = liveness.program_peak_bytes(tl0.program, feed0, tl0._scope, [l0])
    p1 = liveness.program_peak_bytes(tl1.program, feed1, tl1._scope, [l1])
    assert p1 < p0, "recompute did not lower peak: %d >= %d" % (p1, p0)
    out["longctx_peak_live_mb"] = round(p0 / 2 ** 20, 3)
    out["longctx_peak_live_recompute_mb"] = round(p1 / 2 ** 20, 3)
    out["longctx_recompute_saving_pct"] = round(100 * (1 - p1 / p0), 1)

    # -- 4. sequence-sharded decode identity ----------------------------
    SRC, PROMPT, CAP = 16, 8, 16
    rng = np.random.RandomState(3)
    src = rng.randint(2, V, (2, SRC)).astype(np.int64)
    prompt = rng.randint(2, V, (2, PROMPT)).astype(np.int64)
    plens = np.array([PROMPT, PROMPT - 2], np.int64)

    def gen(seq_shards):
        with dygraph.guard():
            model = transformer.Transformer.tiny(V, V)
            prng = np.random.RandomState(11)
            for _, p in model.named_parameters():
                p.set_value(prng.uniform(-0.3, 0.3,
                                         p.shape).astype(np.float32))
            sess = transformer.build_decode_session(
                model, 2, SRC, PROMPT, CAP, end_id=1,
                seq_shards=seq_shards)
        t0 = time.perf_counter()
        toks, _ = sess.generate(src, prompt, plens, 12)
        return toks, time.perf_counter() - t0

    toks1, t1 = gen(1)
    toks4, t4 = gen(4)
    assert np.array_equal(toks1, toks4), (
        "sequence-sharded decode diverged from the unsharded session")
    out["longctx_decode_identical"] = True
    out["longctx_decode_unsharded_s"] = round(t1, 3)
    out["longctx_decode_4shard_s"] = round(t4, 3)
    return out


def bench_multihost(warmup=3, iters=10, grad_mb=4):
    """Hierarchical-DP scaling curve (opt-in BENCH_MULTIHOST=1, the
    MULTICHIP_r06 shape): simulate H hosts x D devices over the local
    device set for H in 1,2,4 and measure (a) steps/sec of an MLP
    trained under ``HierarchicalGradAllReduce`` on the ("host",
    "device") mesh and (b) the per-phase ici/dcn seconds+bytes of a
    ``CrossHostGradSync`` allreduce over a ``grad_mb``-MB gradient,
    with and without DGC top-k compression of the DCN phase — the
    ici/dcn split and the DGC byte reduction are the two numbers the
    DCN story stands on."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, monitor, optimizer
    from paddle_tpu.fluid.transpiler.collective import (
        HierarchicalGradAllReduce)
    from paddle_tpu.parallel import CrossHostGradSync

    ndev = len(jax.devices())
    out = {"multihost_devices": ndev}
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(64, 64).astype(np.float32),
            "y": rng.rand(64, 1).astype(np.float32)}
    for hosts in (1, 2, 4):
        if ndev % hosts or hosts > ndev:
            continue
        dev_per_host = ndev // hosts
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        with fluid.program_guard(main, startup):
            x = layers.data("x", shape=[64], dtype="float32")
            y = layers.data("y", shape=[1], dtype="float32")
            h = layers.fc(x, size=256, act="relu")
            p = layers.fc(h, size=1)
            loss = layers.mean(layers.square(p - y))
            optimizer.SGD(0.01).minimize(loss)
        HierarchicalGradAllReduce(nranks=ndev).transpile(startup, main)
        compiled = fluid.CompiledProgram(main).with_explicit_collectives(
            loss_name=loss.name, mesh_axes=("host", "device"),
            mesh_shape={"host": hosts, "device": dev_per_host})
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(warmup):
                exe.run(compiled, feed=feed, fetch_list=[loss])
            t0 = time.perf_counter()
            for _ in range(iters):
                (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
            jax.block_until_ready(lv)
            step_s = (time.perf_counter() - t0) / iters
        out["multihost_h%d_steps_per_sec" % hosts] = round(1.0 / step_s, 2)

        # phase-attributed allreduce, dense vs DGC-compressed DCN
        n = grad_mb * (1 << 20) // 4
        grad = rng.rand(hosts, dev_per_host, n).astype(np.float32)
        for tag, ratio in (("dense", None), ("dgc", 0.01)):
            monitor.reset()
            sync = CrossHostGradSync(hosts, dev_per_host, dgc_ratio=ratio)
            for _ in range(warmup):
                sync.allreduce([grad])
            monitor.reset()
            t0 = time.perf_counter()
            for _ in range(iters):
                sync.allreduce([grad])
            total = time.perf_counter() - t0
            dump = monitor.dump_json()
            sec = {e["labels"]["phase"]: e["sum"]
                   for e in dump["crosshost_allreduce_seconds"]}
            byt = {e["labels"]["phase"]: e["value"]
                   for e in dump["crosshost_allreduce_bytes_total"]}
            pre = "multihost_h%d_%s" % (hosts, tag)
            out[pre + "_allreduce_ms"] = round(total / iters * 1e3, 3)
            out[pre + "_ici_seconds"] = round(sec.get("ici", 0.0), 4)
            out[pre + "_dcn_seconds"] = round(sec.get("dcn", 0.0), 4)
            out[pre + "_dcn_bytes_per_step"] = \
                int(byt.get("dcn", 0) // iters)
    return out


def bench_deepfm(batch_size=4096, warmup=20, iters=2000):
    """BASELINE config 4 (DeepFM CTR examples/sec/chip); opt-in via
    BENCH_DEEPFM=1. Embedding-gather dominated — the number that matters
    is examples/sec, not MFU. Steps are ~3.8 ms, so the window is LONG
    (2000 iters ≈ 7.5 s x2): 40-iter windows swung 0.48-0.86M ex/s
    run-to-run; at 2000+ iters repeated runs agree within 0.1%
    (1.0865M vs 1.0854M, r5). The windows run step-batched
    (exe.run(..., iters=k)) so host CPU contention — which cost 20% at
    one dispatch per step — stays out of the number."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import deepfm

    import jax

    cfg = deepfm.DeepFMConfig()
    main, startup, loss, _auc = deepfm.build_train_program(cfg)
    exe = fluid.Executor()
    feed = {k: jax.device_put(v)
            for k, v in deepfm.synthetic_batch(cfg, batch_size).items()}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(warmup):
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()
        eps, _, step_s = _stable_throughput(
            exe, main, feed, loss, iters, jax, batch_size,
            "deepfm examples/sec", use_iters=True)
    return {"deepfm_examples_per_sec": round(eps, 1),
            "deepfm_step_time_ms": round(step_s * 1e3, 3),
            "deepfm_batch_size": batch_size,
            "deepfm_sparse_dim": cfg.sparse_feature_dim}


def bench_embedding(batch_size=256, steps=30, budget=4096,
                    vocab_multiple=16):
    """Sparse embedding engine bench (opt-in BENCH_EMBED=1): DeepFM
    trains with its big table on a HostEmbeddingTable whose vocabulary is
    ``vocab_multiple``x the simulated HBM-resident budget (>= the 10x
    acceptance bar). Every step draws a fresh id batch, so the residency
    engine admits/evicts continuously and the async prefetch overlap is
    exercised for real. Reports steps/sec with and without prefetch,
    lookup-latency p50/p99 from the monitor histogram, and asserts the
    compile bound: grow()ing the vocabulary mid-run adds ZERO compile
    cache misses."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import embedding
    from paddle_tpu.fluid import monitor
    from paddle_tpu.models import deepfm

    vocab = vocab_multiple * budget
    cfg = deepfm.DeepFMConfig(sparse_feature_dim=vocab, num_fields=8,
                              num_dense=8, embedding_size=16,
                              fc_sizes=(64, 64))
    rng = np.random.RandomState(0)

    def fresh_batch():
        return {
            "sparse_ids": rng.randint(0, vocab, (batch_size, 8))
            .astype(np.int64),
            "dense_x": rng.rand(batch_size, 8).astype(np.float32),
            "label": rng.randint(0, 2, (batch_size, 1)).astype(np.int64),
        }

    embedding.reset_tables()
    table = embedding.HostEmbeddingTable(
        "fm_emb", num_rows=vocab, dim=cfg.embedding_size,
        resident_budget=budget, seed=1)
    main, startup, loss, _ = deepfm.build_train_program(cfg,
                                                        residence="host")
    exe = fluid.Executor()
    misses = monitor.counter("executor_compile_cache_miss_total")
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(3):  # warmup / compile
                (lv,) = exe.run(main, feed=fresh_batch(),
                                fetch_list=[loss])
                assert np.isfinite(np.asarray(lv)).all()

            def timed(n, prefetch):
                feeds = [fresh_batch() for _ in range(n + 1)]
                t0 = time.perf_counter()
                for i in range(n):
                    (lv,) = exe.run(main, feed=feeds[i],
                                    fetch_list=[loss],
                                    return_numpy=False)
                    if prefetch:
                        # stage batch i+1's missing rows while step i's
                        # device compute is still in flight
                        embedding.prefetch(main, feeds[i + 1])
                assert np.isfinite(np.asarray(lv)).all()
                return n / (time.perf_counter() - t0)

            sps_cold = timed(steps, prefetch=False)
            sps = timed(steps, prefetch=True)

            # compile bound: doubling the vocabulary mid-run must not
            # retrace — the step is keyed on the budget, never the vocab
            warm_misses = misses.value
            table.grow(2 * vocab)
            # ids stay inside the original range: DeepFM's tiny
            # first-order device table shares the same id feed and
            # cannot grow (grown-range lookups there are exercised by
            # the dedicated engine test instead)
            for _ in range(3):
                (lv,) = exe.run(main, feed=fresh_batch(),
                                fetch_list=[loss])
                assert np.isfinite(np.asarray(lv)).all()
            assert misses.value == warm_misses, (
                "vocabulary growth retraced the program: %d extra "
                "compiles" % (misses.value - warm_misses))

        lookup_h = monitor.histogram("embedding_lookup_seconds",
                                     labels={"table": "fm_emb"})
        hits = monitor.counter("embedding_prefetch_hit_total",
                               labels={"table": "fm_emb"}).value
        evictions = monitor.counter("embedding_evictions_total",
                                    labels={"table": "fm_emb"}).value
        assert hits > 0, "prefetch never hit — overlap path not exercised"
        assert evictions > 0, "no evictions — budget not under pressure"
        return {
            "embed_deepfm_steps_per_sec": round(sps, 2),
            "embed_deepfm_steps_per_sec_no_prefetch": round(sps_cold, 2),
            "embed_examples_per_sec": round(sps * batch_size, 1),
            "embed_lookup_p50_ms": round(
                1e3 * (lookup_h.quantile(0.5) or 0), 3),
            "embed_lookup_p99_ms": round(
                1e3 * (lookup_h.quantile(0.99) or 0), 3),
            "embed_vocab_rows": table.num_rows,
            "embed_resident_budget": budget,
            "embed_vocab_over_budget": round(table.num_rows / budget, 1),
            "embed_prefetch_hits": hits,
            "embed_evictions": evictions,
            "embed_batch_size": batch_size,
        }
    finally:
        embedding.reset_tables()


def transformer_train_flops_per_step(batch, s, d, di, L, V):
    """Analytic matmul FLOPs for one Transformer train step (fwd+bwd ~3x):
    per layer qkvo projections + attention matmuls + FFN, encoder and
    decoder stacks (decoder adds cross-attention), plus the vocab head.
    (Head count cancels out of the attention matmul FLOPs.)"""
    attn_proj = 4 * 2 * batch * s * d * d
    attn_mm = 4 * batch * s * s * d
    ffn = 2 * 2 * batch * s * d * di
    enc_layer = attn_proj + attn_mm + ffn
    dec_layer = 2 * (attn_proj + attn_mm) + ffn
    head = 2 * batch * s * d * V
    return 3 * (L * enc_layer + L * dec_layer + head)


def bench_transformer(batch_size=32, seq_len=64, warmup=3, iters=10):
    """BASELINE config 5 (Transformer-big, dygraph tracer -> XLA JIT);
    opt-in via BENCH_TRANSFORMER=1. The model runs eagerly once under the
    dygraph tracer, the recorded Program gets a loss + Adam appended, and
    the static step is what's timed — the reference's to-static flow."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph, layers, optimizer
    from paddle_tpu.fluid.contrib import mixed_precision
    from paddle_tpu.models import transformer

    import jax

    V, d, di, L = 32000, 1024, 4096, 6  # Transformer.big (16 heads)
    with dygraph.guard():
        model = transformer.Transformer.big(V, V)
        src, tgt, labels, pos = transformer.synthetic_batch(
            V, V, batch_size, seq_len)
        bias = transformer.make_causal_bias(seq_len)
        args = [dygraph.to_variable(v) for v in (src, tgt, pos, pos, bias)]
        _, traced = dygraph.jit.trace(model, args)

    startup = fluid.Program()
    with fluid.program_guard(traced.program, startup):
        logits = traced.program.global_block().var(traced._fetch_names[0])
        label = layers.data("tfm_label", [seq_len, 1], dtype="int64")
        flat = layers.reshape(logits, [-1, V])
        ce = layers.softmax_with_cross_entropy(
            flat, layers.reshape(label, [-1, 1]))
        loss = layers.mean(ce)
        opt = mixed_precision.decorate(optimizer.Adam(learning_rate=1e-4))
        opt.minimize(loss)

    traced._materialize_scope()
    feed = {n: jax.device_put(v) for n, v in
            zip(traced._feed_names, (src, tgt, pos, pos, bias))}
    feed["tfm_label"] = jax.device_put(labels)
    exe = fluid.Executor()
    from paddle_tpu.fluid.executor import scope_guard

    with scope_guard(traced._scope):
        # params came from the eager trace; optimizer/AMP state initializes
        # through the startup program minimize() populated
        exe.run(startup)
        for _ in range(warmup):
            (lv,) = exe.run(traced.program, feed=feed, fetch_list=[loss])
            assert np.isfinite(np.asarray(lv)).all()
        tps, _, step_s = _stable_throughput(
            exe, traced.program, feed, loss, iters, jax,
            batch_size * seq_len, "transformer tokens/sec")
    step_ms = step_s * 1e3
    flops = transformer_train_flops_per_step(batch_size, seq_len, d, di,
                                             L, V)
    peak, peak_source = _peak_flops(jax.devices()[0])
    mfu = flops / (step_ms / 1e3) / peak
    assert mfu <= 1.0, "transformer MFU %.3f > 1" % mfu
    return {"transformer_big_tokens_per_sec": round(tps, 1),
            "transformer_big_step_time_ms": round(step_ms, 3),
            "transformer_big_mfu": round(mfu, 4),
            "transformer_big_peak_source": peak_source,
            "transformer_big_batch_size": batch_size,
            "transformer_big_seq_len": seq_len}


def _build_tower_pipeline(n_layers, n_stages, trace_batch, seq_len, vocab,
                          d_model=64, n_heads=4, d_inner=128, lr=0.1,
                          num_microbatches=4, seed=7):
    """Trace an EncoderTower LM at per-shard microbatch size, cut it into
    ``n_stages`` uniform segments at encoder-layer boundaries, and wrap
    it with ``with_pipeline``. Returns (traced, startup, loss, compiled,
    feed_fn) where feed_fn(batch_rows, seed) builds a full-batch feed."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph, layers, optimizer
    from paddle_tpu.models import transformer

    import jax

    with dygraph.guard():
        model = transformer.EncoderTower(
            vocab, d_model=d_model, n_heads=n_heads, d_inner=d_inner,
            n_layers=n_layers, max_len=seq_len, dropout_rate=0.0)
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, vocab, size=(trace_batch, seq_len),
                          ).astype("int64")
        pos = np.tile(np.arange(seq_len, dtype="int64"), (trace_batch, 1))
        args = [dygraph.to_variable(v) for v in (ids, pos)]
        _, traced = dygraph.jit.trace(model, args)

    startup = fluid.Program()
    with fluid.program_guard(traced.program, startup):
        blk = traced.program.global_block()
        logits = blk.var(traced._fetch_names[0])
        label = layers.data("tower_lbl", [seq_len, 1], dtype="int64")
        ce = layers.softmax_with_cross_entropy(
            layers.reshape(logits, [-1, vocab]),
            layers.reshape(label, [-1, 1]))
        loss = layers.mean(ce)
        opt = optimizer.SGD(learning_rate=lr)
        if n_stages > 1:
            per = n_layers // n_stages
            cuts = [blk.var(model.last_checkpoints[per * (i + 1) - 1])
                    for i in range(n_stages - 1)]
            opt = optimizer.PipelineOptimizer(opt, cut_list=cuts)
        opt.minimize(loss)
    traced._materialize_scope()

    compiled = fluid.CompiledProgram(traced.program).with_pipeline(
        loss_name=loss.name, places=jax.devices()[:n_stages],
        num_microbatches=num_microbatches)

    def feed_fn(batch_rows, fseed=11):
        frng = np.random.RandomState(fseed)
        fids = frng.randint(0, vocab, size=(batch_rows, seq_len),
                            ).astype("int64")
        fpos = np.tile(np.arange(seq_len, dtype="int64"), (batch_rows, 1))
        flbl = frng.randint(0, vocab, size=(batch_rows, seq_len, 1),
                            ).astype("int64")
        feed = dict(zip(traced._feed_names, (fids, fpos)))
        feed["tower_lbl"] = flbl
        return feed

    return traced, startup, loss, compiled, feed_fn


def bench_pipeline(seq_len=32, vocab=256, layers_per_stage=2, mb_rows=4,
                   warmup=2, iters=8):
    """3D-parallelism bench (opt-in BENCH_PIPELINE=1), CPU-mesh friendly.

    Two measurements:
      * bubble fraction — a fixed 2-stage pipeline timed at two
        microbatch counts (M=4 and M=8). The per-tick time comes from
        the slope (T(M2)-T(M1))/(M2-M1), which cancels the fixed
        per-step overhead; the measured bubble (S-1)*tick/T(M) must
        match the analytic (S-1)/(M+S-1) within 10 points, and the
        ``pipeline_bubble_fraction`` gauge must equal the analytic
        value exactly (it is set from the schedule shape at wrap).
      * weak scaling — 1 -> 2 -> 4 stages with ``layers_per_stage``
        encoder layers per stage (the model grows with the mesh), so
        ideal scaling is flat tokens/sec; reported, not asserted.
    """
    from paddle_tpu.fluid import monitor

    import paddle_tpu.fluid as fluid

    def run_config(n_stages, M):
        traced, startup, loss, compiled, feed_fn = _build_tower_pipeline(
            n_layers=layers_per_stage * n_stages, n_stages=n_stages,
            trace_batch=mb_rows, seq_len=seq_len, vocab=vocab,
            num_microbatches=M)
        B = M * mb_rows
        feed = feed_fn(B)
        exe = fluid.Executor()
        with fluid.scope_guard(traced._scope):
            exe.run(startup)
            for _ in range(warmup):
                (lv,) = exe.run(compiled, feed=feed, fetch_list=[loss])
                assert np.isfinite(np.asarray(lv)).all()
            t0 = time.perf_counter()
            for _ in range(iters):
                exe.run(compiled, feed=feed, fetch_list=[loss])
            dt = (time.perf_counter() - t0) / iters
        gauge = monitor.gauge("pipeline_bubble_fraction").value
        return dt, B * seq_len / dt, gauge

    # -- bubble fraction: same 2-stage model, two microbatch counts ------
    S, M1, M2 = 2, 4, 8
    t1, _, g1 = run_config(S, M1)
    t2, _, g2 = run_config(S, M2)
    tick = (t2 - t1) / ((M2 + S - 1) - (M1 + S - 1))
    analytic1 = (S - 1) / (M1 + S - 1)
    analytic2 = (S - 1) / (M2 + S - 1)
    measured = (S - 1) * tick / t1 if tick > 0 else 0.0
    assert g1 == analytic1 and g2 == analytic2, (
        "pipeline_bubble_fraction gauge %r/%r != analytic %r/%r"
        % (g1, g2, analytic1, analytic2))
    assert abs(measured - analytic1) <= 0.10, (
        "measured bubble %.3f vs analytic %.3f: off by more than 10 "
        "points" % (measured, analytic1))

    # -- weak scaling: layers grow with the stage count ------------------
    weak = {}
    for n_stages in (1, 2, 4):
        _, tps, _ = run_config(n_stages, M=8)
        weak["pipeline_weak_tokens_per_sec_%dstage" % n_stages] = (
            round(tps, 1))

    out = {"pipeline_bubble_analytic": round(analytic1, 4),
           "pipeline_bubble_measured": round(measured, 4),
           "pipeline_bubble_gauge": g1,
           "pipeline_tick_seconds": round(tick, 6),
           "pipeline_microbatches_total":
               monitor.counter("pipeline_microbatches_total").value}
    out.update(weak)
    return out


def bench_transformer_decode(batch_sizes=(1, 64), src_len=128,
                             prompt_len=64, cache_capacity=1024,
                             new_tokens=64):
    """Autoregressive greedy decode through the KV-cache fast path
    (opt-in BENCH_DECODE=1). Per batch size: build a Transformer-big
    decode session (ring capacity 1024 — the Pallas decode-kernel
    regime), time the prefill once and the per-token decode loop
    separately, and report GENERATED tokens/sec. The decode program
    never retraces: after the warmup generation the compile-cache miss
    counter must not move, and the full trajectory costs exactly two
    compiles (prefill + decode) — both asserted here, both visible in
    the JSON's monitor sub-dict (decode_steps_total climbs, misses
    don't)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph, monitor
    from paddle_tpu.models import transformer

    out = {}
    for B in batch_sizes:
        with dygraph.guard():
            model = transformer.Transformer.big()
            m0 = monitor.counter("executor_compile_cache_miss_total").value
            sess = transformer.build_decode_session(
                model, B, src_len, prompt_len, cache_capacity, end_id=1)
            rng = np.random.RandomState(0)
            src = rng.randint(2, 32000, (B, src_len)).astype(np.int64)
            prompt = rng.randint(2, 32000,
                                 (B, prompt_len)).astype(np.int64)
            plens = np.full((B,), prompt_len, np.int64)

            sess.generate(src, prompt, plens, 2)  # compile both programs
            m1 = monitor.counter("executor_compile_cache_miss_total").value
            assert m1 - m0 == 2, (
                "decode session cost %d compiles, want 2 (prefill + "
                "decode)" % (m1 - m0))

            t0 = time.perf_counter()
            sess.generate(src, prompt, plens, 1)  # prefill + argmax only
            t_prefill = time.perf_counter() - t0
            dec_hist = monitor.get_metric("decode_step_seconds")
            disp0 = dec_hist.sum if dec_hist is not None else 0.0
            t0 = time.perf_counter()
            toks, _ = sess.generate(src, prompt, plens, new_tokens)
            t_full = time.perf_counter() - t0
            dec_hist = monitor.get_metric("decode_step_seconds")
            disp1 = dec_hist.sum if dec_hist is not None else 0.0
            t0 = time.perf_counter()
            toks2, _ = sess.generate(src, prompt, plens, 2 * new_tokens)
            t_full2 = time.perf_counter() - t0
            m2 = monitor.counter("executor_compile_cache_miss_total").value
            assert m2 == m1, (
                "decode steps retraced: %d extra compiles" % (m2 - m1))
            assert (toks2[:, :new_tokens] == toks).all(), (
                "decode is not deterministic across generations")

        step_s = (t_full2 - t_full) / (B * new_tokens)  # marginal token
        rate = 1.0 / max(step_s, 1e-12)
        r1 = B * (new_tokens - 1) / max(t_full - t_prefill, 1e-12)
        tag = "_batch%d" % B
        out["transformer_decode_tokens_per_sec" + tag] = round(rate, 1)
        out["transformer_decode_tokens_per_sec_short_window" + tag] = \
            round(r1, 1)
        out["transformer_decode_prefill_ms" + tag] = \
            round(t_prefill * 1e3, 3)
        out["transformer_decode_step_ms" + tag] = \
            round(step_s * B * 1e3, 3)
        out["transformer_decode_compile_misses" + tag] = m1 - m0
        # per-phase breakdown (PROFILE_r06 debt): where a full generation
        # spends its wall clock. Decode dispatch is async, so the device
        # sync cost pools at the host boundary — the final token
        # materialization — not in the per-step dispatch times.
        dispatch_s = max(0.0, disp1 - disp0)
        out["transformer_decode_phases" + tag] = {
            "prefill_ms": round(t_prefill * 1e3, 3),
            "decode_dispatch_ms": round(dispatch_s * 1e3, 3),
            "host_boundary_ms": round(
                max(0.0, t_full - t_prefill - dispatch_s) * 1e3, 3),
        }
    # headline: the throughput-oriented batch (the last one)
    out["transformer_decode_tokens_per_sec"] = \
        out["transformer_decode_tokens_per_sec_batch%d" % batch_sizes[-1]]
    out["transformer_decode_new_tokens"] = new_tokens
    out["transformer_decode_prompt_len"] = prompt_len
    out["transformer_decode_cache_capacity"] = cache_capacity
    # the paged/prefix/speculative engine legs (ROADMAP decode metrics)
    from paddle_tpu.models import transformer as _tf
    out.update(bench_decode_engine(
        _tf.Transformer.big, 32000, width=8, src_len=src_len,
        prompt_len=prompt_len, cache_capacity=cache_capacity,
        page_tokens=cache_capacity // 8))
    return out


def bench_decode_engine(model_fn, vocab, width=8, src_len=128,
                        prompt_len=64, cache_capacity=1024,
                        page_tokens=128, pool_frac=0.375, spec_k=4,
                        spec_new_tokens=12, prefix_joins=6,
                        hbm_budget_gb=32.0):
    """The decode ENGINE legs of BENCH_DECODE — the ROADMAP's missing
    serving metrics:

    * concurrent-streams-per-HBM-budget, paged vs dense, from the
      utils/liveness.py peak-bytes estimator over one decode dispatch
      (feeds + state). The dense stream pays width x capacity ring
      caches whether slots are live or not; the paged pool is sized to
      ``pool_frac`` of that (the continuous-batching regime: admitted
      prompts plus growth headroom), so the same budget seats strictly
      more streams — asserted.
    * prefix-hit prefill tokens/sec on a shared-prefix workload: every
      request carries the same (src, prompt), so after the first join
      the prefill dispatch is skipped and the pages are aliased
      copy-on-write — the hit must beat the miss, asserted, and the
      hits' tokens must match the miss's, asserted.
    * accepted-tokens-per-step for greedy speculative decoding with a
      full-depth self-draft (the acceptance ceiling: proposals always
      match), token-identical to the dense baseline and exactly two
      extra compiles — all asserted."""
    from paddle_tpu.fluid import dygraph, monitor
    from paddle_tpu.models import transformer
    from paddle_tpu.utils.liveness import program_peak_bytes

    out = {}
    rng = np.random.RandomState(7)
    B = width
    with dygraph.guard():
        model = model_fn()
        dense = transformer.build_decode_session(
            model, B, src_len, prompt_len, cache_capacity, end_id=1)
        n_pages = cache_capacity // page_tokens
        pool_pages = max(n_pages + 1, int(B * n_pages * pool_frac) + 1)
        paged = transformer.build_paged_decode_session(
            model, B, src_len, prompt_len, cache_capacity, end_id=1,
            page_tokens=page_tokens, pool_pages=pool_pages,
            prefix_cache_size=8)
        H = model.n_heads
        d = model.d_model // H
        L = dense._L

        # ---- streams per HBM budget (liveness estimator) --------------
        dense_prog = getattr(dense.decode_program, "_program",
                             dense.decode_program)
        dense_feed = dict(zip(dense._decode_feeds, [
            np.zeros((B, 1), np.int32), np.zeros((B, 1), bool),
            np.array([1], np.int32),
            np.full((B,), prompt_len, np.int32),
        ] + [np.zeros((B, H, src_len, d), np.float32)
             for _ in range(2 * L)]
          + [np.zeros((B, H, cache_capacity, d), np.float32)
             for _ in range(2 * L)]))
        dense_peak = program_peak_bytes(dense_prog, dense_feed,
                                       dense.scope,
                                       dense._decode_fetches)
        paged_feed = dict(zip(paged._decode_feeds, [
            np.zeros((B, 1), np.int32), np.zeros((B, 1), bool),
            np.array([1], np.int32), np.ones((B,), np.int32),
            np.zeros((B, n_pages), np.int32),
        ] + [np.zeros((B, H, src_len, d), np.float32)
             for _ in range(2 * L)]
          + [np.zeros((pool_pages, H, page_tokens, d), np.float32)
             for _ in range(2 * L)]))
        paged_peak = program_peak_bytes(paged._decode_traced, paged_feed,
                                        paged.scope,
                                        paged._decode_fetches)
        budget = hbm_budget_gb * float(1 << 30)
        streams_dense = B * budget / max(dense_peak, 1)
        streams_paged = B * budget / max(paged_peak, 1)
        assert streams_paged > streams_dense, (
            "paged decode must seat MORE streams per HBM byte: paged "
            "%.1f vs dense %.1f" % (streams_paged, streams_dense))
        out["decode_hbm_budget_gb"] = hbm_budget_gb
        out["decode_peak_bytes_dense"] = int(dense_peak)
        out["decode_peak_bytes_paged"] = int(paged_peak)
        out["decode_streams_per_hbm_budget_dense"] = round(streams_dense,
                                                           1)
        out["decode_streams_per_hbm_budget_paged"] = round(streams_paged,
                                                           1)
        out["decode_paged_pool_pages"] = pool_pages
        out["decode_page_tokens"] = page_tokens

        # ---- shared-prefix workload ----------------------------------
        src1 = rng.randint(2, vocab, (src_len,)).astype(np.int64)
        pr1 = rng.randint(2, vocab, (prompt_len,)).astype(np.int64)

        def run_one(budget_toks=4):
            t0 = time.perf_counter()
            slot, done = paged.join(src1, pr1,
                                    max_new_tokens=budget_toks)
            t_join = time.perf_counter() - t0
            if done is not None:          # finished at the prefill
                return t_join, np.asarray(done[0])
            toks = None
            while toks is None:
                for s_, toks_, _fin in paged.step():
                    if s_ == slot:
                        toks = toks_
            return t_join, np.asarray(toks)

        t_miss, toks_miss = run_one()
        hit_times, hit_ok = [], True
        for _ in range(prefix_joins - 1):
            t_hit, toks_hit = run_one()
            hit_times.append(t_hit)
            hit_ok = hit_ok and np.array_equal(toks_hit, toks_miss)
        t_hit_mean = sum(hit_times) / len(hit_times)
        assert hit_ok, "prefix-hit tokens diverged from the miss join"
        assert t_hit_mean < t_miss, (
            "prefix hit (%.1f ms) did not amortize the prefill "
            "(%.1f ms)" % (t_hit_mean * 1e3, t_miss * 1e3))
        out["decode_prefix_miss_join_ms"] = round(t_miss * 1e3, 3)
        out["decode_prefix_hit_join_ms"] = round(t_hit_mean * 1e3, 3)
        out["decode_prefix_miss_prefill_tokens_per_sec"] = round(
            prompt_len / t_miss, 1)
        out["decode_prefix_hit_prefill_tokens_per_sec"] = round(
            prompt_len / t_hit_mean, 1)
        out["decode_prefix_hit_speedup"] = round(t_miss / t_hit_mean, 2)

        # ---- speculative: full-depth draft = acceptance ceiling ------
        srcB = rng.randint(2, vocab, (B, src_len)).astype(np.int64)
        prB = rng.randint(2, vocab, (B, prompt_len)).astype(np.int64)
        plensB = np.full((B,), prompt_len, np.int64)
        base_toks, _ = dense.generate(srcB, prB, plensB, spec_new_tokens)
        t0 = time.perf_counter()        # time the WARM baseline pass
        base_toks2, _ = dense.generate(srcB, prB, plensB,
                                       spec_new_tokens)
        t_base = time.perf_counter() - t0
        assert (base_toks2 == base_toks).all()
        hist = monitor.get_metric("decode_spec_accepted_tokens")
        c0, s0 = hist.count, hist.sum
        m0 = monitor.counter("executor_compile_cache_miss_total").value
        spec = transformer.build_speculative_session(
            model, dense, k=spec_k, draft_layers=L)
        spec_toks, _ = spec.generate(srcB, prB, plensB, spec_new_tokens)
        m1 = monitor.counter("executor_compile_cache_miss_total").value
        t0 = time.perf_counter()
        spec_toks2, _ = spec.generate(srcB, prB, plensB, spec_new_tokens)
        t_spec = time.perf_counter() - t0
        m2 = monitor.counter("executor_compile_cache_miss_total").value
        assert m1 - m0 == 2, (
            "speculative session cost %d compiles, want 2 (draft + "
            "verify)" % (m1 - m0))
        assert m2 == m1, "speculative decode retraced on reuse"
        assert (spec_toks == base_toks).all() and \
            (spec_toks2 == base_toks).all(), (
            "speculative decode diverged from the dense baseline")
        accepted = (hist.sum - s0) / max(1, hist.count - c0)
        assert accepted >= 1.5, (
            "greedy speculative accepted %.2f tokens/step, want >= 1.5"
            % accepted)
        out["decode_spec_accepted_tokens_per_step"] = round(accepted, 2)
        out["decode_spec_k"] = spec_k
        out["decode_spec_extra_compiles"] = int(m1 - m0)
        out["decode_spec_tokens_per_sec"] = round(
            B * spec_new_tokens / max(t_spec, 1e-12), 1)
        out["decode_spec_baseline_tokens_per_sec"] = round(
            B * spec_new_tokens / max(t_base, 1e-12), 1)
    return out


def bench_decode_profile(B=4, H=16, d=64, page_tokens=128, n_pages=16,
                         pool_pages=None, iters=20):
    """PROFILE_r06 leg (opt-in BENCH_DECODE_PROFILE=1): per-phase
    timings of the paged decode attention at Pallas-regime geometry
    (capacity = n_pages * page_tokens >= the fused-kernel threshold).

    Phases, timed separately over jitted closures:
    * ``index``: pure page-table indexing — jnp.take of the pool rows
    * ``gather``: index + reshape/transpose to the dense [B, H, C, d]
      layout (everything the fallback path adds before attention)
    * ``softmax_v``: masked online attention over the PRE-gathered
      dense cache (the compute floor)
    * ``paged_kernel``: the fused Pallas paged kernel — table indexing
      via scalar prefetch + gather + softmax*V in one pass (interpret
      mode on CPU; the real kernel on TPU)

    Asserts the profiled path dispatched the Pallas paged kernel
    (attn_kernel_dispatch_total{tier=paged} moved) — the profile must never
    silently measure the fallback — and that kernel output matches the
    gather+reference oracle."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels import attention as A

    C = n_pages * page_tokens
    P = int(pool_pages) if pool_pages else B * n_pages + 1
    scale = 1.0 / float(np.sqrt(d))
    rng = np.random.RandomState(3)
    k_pool = jnp.asarray(
        rng.randn(P, H, page_tokens, d).astype(np.float32))
    v_pool = jnp.asarray(
        rng.randn(P, H, page_tokens, d).astype(np.float32))
    q = jnp.asarray(rng.randn(B, H, 1, d).astype(np.float32))
    perm = rng.permutation(np.arange(1, P))[:B * n_pages]
    table = jnp.asarray(perm.reshape(B, n_pages).astype(np.int32))
    lens = jnp.asarray(np.full((B,), C - 7, np.int32))

    def timeit(fn, *args):
        r = fn(*args)
        jax.block_until_ready(r)        # compile outside the window
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    index = jax.jit(lambda p, t: jnp.take(p, t.reshape(-1), axis=0))
    t_index = timeit(index, k_pool, table)
    gather = jax.jit(A.gather_paged_cache)
    t_gather = timeit(gather, k_pool, table)
    kd = gather(k_pool, table)
    vd = gather(v_pool, table)
    ref = jax.jit(lambda q_, k_, v_, l_: A._ref_attention_cache(
        q_, k_, v_, l_, scale))
    t_attn = timeit(ref, q, kd, vd, lens)

    c0 = monitor.counter("attn_kernel_dispatch_total",
                         labels={"tier": "paged"}).value
    old_force = os.environ.get("PADDLE_TPU_ATTN_FORCE")
    old_interp = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_ATTN_FORCE"] = "paged"
    if jax.devices()[0].platform == "cpu":
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        paged = jax.jit(
            lambda q_, kp, vp, t_, l_: A.paged_attention_cache(
                q_, kp, vp, t_, l_, scale=scale))
        # interpret mode emulates the kernel per-grid-cell in python —
        # seconds per call at real geometry; 2 iters bound the leg's
        # wall-clock without losing the (already unindicative) number
        if jax.devices()[0].platform == "cpu":
            iters, save_iters = min(iters, 2), iters
        t_paged = timeit(paged, q, k_pool, v_pool, table, lens)
        if jax.devices()[0].platform == "cpu":
            iters = save_iters
        err = float(jnp.max(jnp.abs(
            paged(q, k_pool, v_pool, table, lens) - ref(q, kd, vd,
                                                        lens))))
    finally:
        for k, v in (("PADDLE_TPU_ATTN_FORCE", old_force),
                     ("PADDLE_TPU_PALLAS_INTERPRET", old_interp)):
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    c1 = monitor.counter("attn_kernel_dispatch_total",
                         labels={"tier": "paged"}).value
    assert c1 > c0, (
        "profiled path took the gather-dense fallback, not the Pallas "
        "paged kernel — check PADDLE_TPU_ATTN_FORCE/capacity")
    assert err < 1e-4, "paged kernel diverged from oracle by %g" % err
    interpret = jax.devices()[0].platform == "cpu"
    return {
        "decode_profile_geometry": {
            "batch": B, "heads": H, "d_key": d,
            "page_tokens": page_tokens, "n_pages": n_pages,
            "pool_pages": P, "capacity": C,
        },
        "decode_profile_interpret_mode": interpret,
        "decode_profile_index_us": round(t_index * 1e6, 1),
        "decode_profile_gather_us": round(t_gather * 1e6, 1),
        "decode_profile_softmax_v_us": round(t_attn * 1e6, 1),
        "decode_profile_paged_kernel_us": round(t_paged * 1e6, 1),
        "decode_profile_kernel_max_err": err,
        "decode_profile_kernel_dispatches": int(c1 - c0),
    }


def bench_serve(n_clients=64, per_client=8, max_batch_size=16,
                max_queue_delay_ms=1.0, max_req_rows=4):
    """Closed-loop serving-tier load bench (opt-in BENCH_SERVE=1):
    ``n_clients`` threads submit mixed-size requests through the
    dynamic batcher vs. the same request stream through one serialized
    predictor. Reports req/s for both, mean batch occupancy, p50/p99
    latency from the monitor histograms — and asserts the bucket-ladder
    compile bound: after warm-up the recompile counter NEVER moves, no
    matter how many request sizes the stream mixes."""
    import tempfile
    import threading

    import paddle_tpu.fluid as fluid
    from paddle_tpu import inference
    from paddle_tpu.fluid import layers, monitor
    from paddle_tpu.inference import ServeConfig, Server

    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[32], dtype="float32")
        h = layers.fc(x, size=64, act="relu")
        prob = layers.softmax(layers.fc(h, size=8))
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(tmp, ["x"], [prob], exe,
                                      main_program=main)

    rng = np.random.RandomState(0)
    reqs = [rng.rand(rng.randint(1, max_req_rows + 1), 32)
            .astype(np.float32) for _ in range(n_clients * per_client)]
    total_rows = sum(r.shape[0] for r in reqs)

    # serialized baseline: same stream, one request per dispatch, its
    # own predictor warmed over the same ladder (compiles out of the
    # timed window for both sides)
    base = inference.create_predictor(inference.Config(tmp))
    cfg = ServeConfig(max_batch_size=max_batch_size,
                      max_queue_delay_ms=max_queue_delay_ms,
                      max_queue_depth=4 * n_clients)
    # the serial path sees raw request sizes (no bucketing), so warm
    # every size it will serve — compiles stay out of both timed windows
    for b in sorted(set(cfg.ladder()) | set(range(1, max_req_rows + 1))):
        base.run({"x": np.zeros((b, 32), np.float32)})
    t0 = time.perf_counter()
    for r in reqs:
        base.run({"x": r})
    t_serial = time.perf_counter() - t0

    pred = inference.create_predictor(inference.Config(tmp))
    results = {"errors": []}
    with Server() as srv:
        ladder = srv.register("bench", pred, config=cfg,
                              warmup_feed={"x": reqs[0][:1]})
        assert len(pred._seen_sigs) == len(ladder), (
            "warm-up must pre-compile exactly the ladder")
        recompiles0 = monitor.counter(
            "predictor_shape_recompile_total").value

        def client(cid):
            try:
                for i in range(per_client):
                    r = reqs[cid * per_client + i]
                    out = srv.submit("bench",
                                     {"x": r}).result(timeout=120)
                    assert out[0].shape == (r.shape[0], 8)
            except BaseException as e:  # surfaced after join
                results["errors"].append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        t_served = time.perf_counter() - t0
        assert not results["errors"], results["errors"][:3]
        assert len(pred._seen_sigs) == len(ladder), (
            "mixed-size stream grew the signature set past the ladder")
        assert monitor.counter(
            "predictor_shape_recompile_total").value == recompiles0, (
            "mixed-size stream recompiled after warm-up")

    lbl = {"model": "bench"}
    occ = monitor.get_metric("serving_batch_occupancy", labels=lbl)
    e2e = monitor.get_metric("serving_request_seconds", labels=lbl)
    wait = monitor.get_metric("serving_queue_wait_seconds", labels=lbl)
    n = len(reqs)
    return {
        "serve_requests_per_sec": round(n / t_served, 1),
        "serve_serial_requests_per_sec": round(n / t_serial, 1),
        "serve_speedup_vs_serial": round(t_serial / t_served, 3),
        "serve_rows_per_sec": round(total_rows / t_served, 1),
        "serve_mean_batch_occupancy": round(occ.sum / max(occ.count, 1), 4),
        "serve_batches": monitor.get_metric("serving_batches_total",
                                            labels=lbl).value,
        "serve_requests": n,
        "serve_p50_latency_ms": round(1e3 * (e2e.quantile(0.5) or 0), 3),
        "serve_p99_latency_ms": round(1e3 * (e2e.quantile(0.99) or 0), 3),
        "serve_p99_queue_wait_ms": round(1e3 * (wait.quantile(0.99) or 0),
                                         3),
        "serve_shed": monitor.get_metric("serving_shed_total",
                                         labels=lbl).value,
        "serve_bucket_ladder": ladder,
        "serve_clients": n_clients,
        "serve_max_batch_size": max_batch_size,
    }


def _fleet_model_dir(tmp, prelower=True, batch_sizes=(1, 2, 4, 8)):
    """Export the tiny serving model the fleet benches spawn replicas
    on; ``prelower=True`` AOT-compiles the bucket ladder so replica
    processes cold-start with zero live compiles."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 0
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[32], dtype="float32")
        h = layers.fc(x, size=64, act="relu")
        prob = layers.softmax(layers.fc(h, size=8))
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(
            tmp, ["x"], [prob], exe, main_program=main,
            prelower=prelower, prelower_batch_sizes=batch_sizes)
    return tmp


def _fleet_spec(model_dir, delay_ms=2.0, queue_depth=64):
    # breaker_threshold is effectively disabled: the bench wants every
    # over-capacity submit to be a deterministic depth shed, not a
    # breaker-mode fast-reject that depends on shed burstiness
    return {"prefix": "fleet/",
            "models": [{"name": "fc", "model_dir": model_dir,
                        "warmup": {"x": {"shape": [1, 32],
                                         "dtype": "float32"}},
                        "config": {"max_batch_size": 8,
                                   "max_queue_delay_ms": delay_ms,
                                   "max_queue_depth": queue_depth,
                                   "breaker_threshold": 10 ** 6}}]}


def _fleet_closed_loop(router_ep, n_clients, per_client, deadline_ms,
                       max_rows=4, on_request=None):
    """Closed-loop client fleet: ``n_clients`` threads, each with its
    own FleetClient, measuring per-request wall time. Returns
    (ok_in_slo, served, shed, errors, latencies_sec)."""
    import threading

    from paddle_tpu.inference import Overloaded
    from paddle_tpu.serving import FleetClient

    rng = np.random.RandomState(7)
    reqs = [rng.rand(rng.randint(1, max_rows + 1), 32).astype(np.float32)
            for _ in range(n_clients * per_client)]
    state = {"ok_slo": 0, "served": 0, "shed": 0, "errors": [],
             "lat": []}
    mu = threading.Lock()

    def client(cid):
        cli = FleetClient(router_ep)
        try:
            for i in range(per_client):
                r = reqs[cid * per_client + i]
                if on_request is not None:
                    on_request(cid, i)
                t0 = time.perf_counter()
                try:
                    out = cli.submit("fc", {"x": r},
                                     deadline_ms=deadline_ms)
                    dt = time.perf_counter() - t0
                    assert out[0].shape == (r.shape[0], 8)
                    with mu:
                        state["served"] += 1
                        state["lat"].append(dt)
                        if dt <= deadline_ms / 1000.0:
                            state["ok_slo"] += 1
                except Overloaded:
                    with mu:
                        state["shed"] += 1
        except BaseException as e:  # surfaced after join
            with mu:
                state["errors"].append(e)
        finally:
            cli.close()

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    state["wall"] = time.perf_counter() - t0
    return state


def bench_fleet(replica_counts=(1, 2, 4), n_clients=8, per_client=24,
                deadline_ms=500.0, scale_queue_depth=6):
    """``BENCH_FLEET=1``: closed-loop serving-fleet bench. One router +
    subprocess replica fleets of {1, 2, 4} at fixed offered load:
    p50/p99 e2e latency and goodput-under-SLO per size, per-replica
    routed counts proving balance. Each replica's admission bound
    (``max_queue_depth=scale_queue_depth`` rows) is deliberately tight
    enough that a single replica sheds part of the offered load; the
    fleet's capacity is then genuinely the sum of its members, and
    goodput — the fraction of the FIXED offered load answered within
    its deadline — must be monotone non-decreasing 1 -> 4 replicas.
    (The wall-clock rate is reported but not asserted on: on a shared
    machine more processes can coalesce smaller batches and run
    slower per request while still serving strictly MORE of the load
    within SLO.) Then the kill run: SIGKILL one of two replicas
    mid-stream — every request is accounted (served or typed-shed,
    requeues counted), and the supervisor's warm respawn re-registers
    with ZERO live compiles (prelowered ladder + disk hits only)."""
    import json as _json
    import tempfile

    _refuse_children_on_held_chip("bench_fleet")

    from paddle_tpu.distributed.coordination import (CoordClient,
                                                     CoordServer)
    from paddle_tpu.fluid import monitor
    from paddle_tpu.serving import Router
    from paddle_tpu.serving.supervisor import FleetSupervisor

    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    model_dir = _fleet_model_dir(os.path.join(tmp, "model"))
    # scaling leg: per-replica capacity bound, so replicas add capacity;
    # kill leg: generous depth, so sheds reflect the kill alone
    scale_spec = _fleet_spec(model_dir, queue_depth=scale_queue_depth)
    spec = _fleet_spec(model_dir)
    coord = CoordServer().start()
    addr = "%s:%d" % (coord.host, coord.port)
    dbg = CoordClient(addr)
    out = {"fleet_deadline_ms": deadline_ms, "fleet_clients": n_clients,
           "fleet_requests_per_size": n_clients * per_client}

    def wait_members(n, timeout=240):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(dbg.live_members("fleet/replicas/")) >= n:
                return
            time.sleep(0.2)
        raise TimeoutError("only %d/%d replicas registered"
                           % (len(dbg.live_members("fleet/replicas/")), n))

    try:
        goodputs = []
        total = n_clients * per_client
        for n in replica_counts:
            sup = FleetSupervisor(scale_spec, n, addr,
                                  env={"PADDLE_FLEET_LEASE_TTL": "3.0"},
                                  log_dir=os.path.join(tmp, "logs%d" % n))
            router = Router(coord_addr=addr, refresh_interval=0.1)
            try:
                sup.start()
                wait_members(n)
                router.start()
                st = _fleet_closed_loop(
                    "%s:%d" % (router.host, router.port),
                    n_clients, per_client, deadline_ms)
                assert not st["errors"], st["errors"][:3]
                lat = sorted(st["lat"])
                # goodput-under-SLO: fraction of the fixed offered load
                # answered within its deadline — the quantity that is
                # monotone in fleet capacity
                goodput = st["ok_slo"] / total
                goodputs.append(goodput)
                per_rep = {
                    rid: monitor.counter("fleet_replica_routed_total",
                                         labels={"replica": rid}).value
                    for rid in sup.replica_ids()}
                out["fleet_%dx_goodput" % n] = round(goodput, 3)
                out["fleet_%dx_rate_rps" % n] = round(
                    st["served"] / st["wall"], 1)
                out["fleet_%dx_p50_ms" % n] = round(
                    1e3 * lat[len(lat) // 2], 3) if lat else None
                out["fleet_%dx_p99_ms" % n] = round(
                    1e3 * lat[int(len(lat) * 0.99) - 1], 3) if lat else None
                out["fleet_%dx_served" % n] = st["served"]
                out["fleet_%dx_shed" % n] = st["shed"]
                out["fleet_%dx_per_replica" % n] = per_rep
                if n > 1:
                    assert all(v > 0 for v in per_rep.values()), (
                        "unbalanced fleet: %s" % per_rep)
            finally:
                router.close()
                sup.stop(timeout=60)
        # the load must actually saturate ONE replica, else "more
        # replicas do not hurt" would be vacuously true
        assert goodputs[0] < 1.0, (
            "offered load never exceeded a single replica's admission "
            "bound; tighten scale_queue_depth or raise n_clients")
        assert all(b >= a - 0.02 for a, b in zip(goodputs, goodputs[1:])), (
            "goodput-under-SLO regressed with more replicas: %s"
            % [round(g, 3) for g in goodputs])

        # -- kill-one-replica: zero loss, warm respawn ------------------
        sup = FleetSupervisor(spec, 2, addr,
                              env={"PADDLE_FLEET_LEASE_TTL": "3.0"},
                              log_dir=os.path.join(tmp, "logs_kill"))
        router = Router(coord_addr=addr, refresh_interval=0.1)
        try:
            sup.start()
            wait_members(2)
            router.start()
            requeued0 = monitor.counter("fleet_requeued_total").value
            shed0 = monitor.sum_labeled("fleet_shed_total")
            victim = sup.replica_ids()[0]
            pid0 = sup.pid(victim)
            killed = {"done": False}

            def killer(cid, i):
                # first client, a third of the way in: pull the plug
                if cid == 0 and i == per_client // 3 \
                        and not killed["done"]:
                    killed["done"] = True
                    sup.kill(victim)

            st = _fleet_closed_loop(
                "%s:%d" % (router.host, router.port),
                n_clients, per_client, deadline_ms, on_request=killer)
            assert not st["errors"], st["errors"][:3]
            total = n_clients * per_client
            assert st["served"] + st["shed"] == total, (
                "lost requests: %d served + %d shed != %d"
                % (st["served"], st["shed"], total))
            out["fleet_kill_served"] = st["served"]
            out["fleet_kill_shed"] = (
                monitor.sum_labeled("fleet_shed_total") - shed0)
            out["fleet_kill_requeued"] = (
                monitor.counter("fleet_requeued_total").value - requeued0)
            # the supervisor respawned the victim warm: same id, new
            # pid, ZERO live compiles (prelowered ladder off disk)
            deadline = time.time() + 240
            info = None
            while time.time() < deadline:
                blob = dbg.get("fleet/replicas/%s" % victim)
                if blob is not None:
                    info = _json.loads(blob.decode())
                    if info["pid"] != pid0:
                        break
                time.sleep(0.2)
            assert info is not None and info["pid"] != pid0, (
                "victim %s never respawned" % victim)
            assert info["live_compiles"] == 0, info
            out["fleet_respawn_live_compiles"] = info["live_compiles"]
            out["fleet_respawn_warmup_disk_hits"] = \
                info["warmup_disk_hits"]
            out["fleet_respawns"] = sup.respawns
        finally:
            router.close()
            sup.stop(timeout=60)
    finally:
        dbg.close()
        coord.stop()
    return out


def bench_coord_recovery(smoke=False, n_clients=None, per_client=None,
                         deadline_ms=10000.0, model_dir=None):
    """``BENCH_COORD=1``: kill the coordination service mid-run —
    ``CoordServer.crash()``, the in-process equivalent of kill -9: no
    drain, no final snapshot, every connection severed — and restart it
    on the SAME port against the SAME WAL dir while a closed-loop
    client fleet keeps hammering a 2-replica serving fleet. The data
    path never touches the coordinator, so the run must lose ZERO
    requests; the control path degrades visibly and recovers:

      * the router detects the outage (its fail-fast coordination
        client) and keeps routing over the last-known replica set —
        the chaos thread holds the outage open until
        ``fleet_stale_routing_total`` proves requests rode the stale
        view;
      * the restarted server replays its WAL (replica leases included,
        as wall-clock deadlines) at a bumped epoch; replica clients
        re-dial transparently, replay their leases, re-register;
      * the router's next successful refresh clears the stale flag.

    Reported: the outage window (crash -> restarted), the stale-routing
    window (first stale-routed request -> router fresh again), full
    recovery time (crash -> fresh), stale-routed count (must be > 0)
    and requests lost (must be 0)."""
    import tempfile
    import threading

    from paddle_tpu.distributed.coordination import (CoordClient,
                                                     CoordServer)
    from paddle_tpu.fluid import monitor
    from paddle_tpu.serving import Replica, Router

    if n_clients is None:
        n_clients = 2 if smoke else 6
    if per_client is None:
        per_client = 40 if smoke else 48
    # pacing keeps the closed loop alive well past the router's ~1 s
    # outage-detection latency (its coordination client's fail-fast
    # grace), so stale routing is actually exercised, not raced
    pace_s = 0.07 if smoke else 0.05
    tmp = tempfile.mkdtemp(prefix="bench_coord_")
    if model_dir is None:
        model_dir = _fleet_model_dir(os.path.join(tmp, "model"),
                                     prelower=False)
    wal_dir = os.path.join(tmp, "wal")
    spec = _fleet_spec(model_dir)
    coord = CoordServer(wal_dir=wal_dir).start()
    addr = "%s:%d" % (coord.host, coord.port)
    port = coord.port
    epoch0 = coord.epoch
    state = {"coord": coord}
    reps = []
    router = None
    dbg = CoordClient(addr)
    stale0 = monitor.counter("fleet_stale_routing_total").value
    try:
        reps = [Replica(spec, coord_addr=addr,
                        replica_id="cr%d" % i, lease_ttl=5.0,
                        stats_interval=0.1).start()
                for i in range(2)]
        deadline = time.time() + 240
        while len(dbg.live_members("fleet/replicas/")) < 2:
            if time.time() > deadline:
                raise TimeoutError("replicas never registered")
            time.sleep(0.1)
        router = Router(coord_addr=addr, refresh_interval=0.1).start()
        kill_ev = threading.Event()
        marks = {}

        def chaos():
            kill_ev.wait(120)
            marks["t_kill"] = time.perf_counter()
            state["coord"].crash()
            # hold the outage open until the router provably routed
            # over its stale table (bounded: the closed loop outlasts
            # this by construction, but a wedge must not hang forever)
            hold = time.time() + 30
            while time.time() < hold:
                if monitor.counter(
                        "fleet_stale_routing_total").value > stale0:
                    break
                time.sleep(0.02)
            marks["t_stale"] = time.perf_counter()
            state["coord"] = CoordServer(port=port,
                                         wal_dir=wal_dir).start()
            marks["t_up"] = time.perf_counter()
            hold = time.time() + 60
            while time.time() < hold:
                with router._table_mu:
                    fresh = router._stale_since is None
                if fresh and router.members():
                    marks["t_fresh"] = time.perf_counter()
                    return
                time.sleep(0.02)

        ct = threading.Thread(target=chaos, daemon=True)
        ct.start()

        def pacer(cid, i):
            time.sleep(pace_s)
            if cid == 0 and i == per_client // 3:
                kill_ev.set()

        st = _fleet_closed_loop(
            "%s:%d" % (router.host, router.port),
            n_clients, per_client, deadline_ms, on_request=pacer)
        ct.join(120)
        assert not st["errors"], st["errors"][:3]
        total = n_clients * per_client
        lost = total - st["served"] - st["shed"]
        assert lost == 0, (
            "lost requests across the coordinator outage: %d served + "
            "%d shed != %d" % (st["served"], st["shed"], total))
        stale_routed = monitor.counter(
            "fleet_stale_routing_total").value - stale0
        assert stale_routed > 0, (
            "no request ever rode the stale routing table — the outage "
            "never overlapped the load")
        assert "t_fresh" in marks, (
            "router never returned to a fresh view: %s" % marks)
        epoch1 = state["coord"].epoch
        assert epoch1 == epoch0 + 1, (epoch0, epoch1)
        return {
            "coord_requests_total": total,
            "coord_requests_served": st["served"],
            "coord_requests_shed": st["shed"],
            "coord_requests_lost": lost,
            "coord_stale_routed": int(stale_routed),
            "coord_outage_s": round(marks["t_up"] - marks["t_kill"], 3),
            "coord_stale_window_s": round(
                marks["t_fresh"] - marks["t_stale"], 3),
            "coord_recovery_s": round(
                marks["t_fresh"] - marks["t_kill"], 3),
            "coord_epochs": [epoch0, epoch1],
        }
    finally:
        if router is not None:
            router.close()
        for r in reps:
            r.drain(timeout=10)
        dbg.close()
        state["coord"].stop()


def bench_restart():
    """``BENCH_RESTART=1``: restart-to-first-step and serving
    ``register()`` warm-up, cold (empty persistent compile cache) vs
    warm (populated) — the two downtime windows the on-disk AOT tier
    (fluid/compile_cache.py) exists to shrink. Each "restart" is a
    fresh Executor + a rebuilt program (``unique_name.guard`` makes the
    rebuild byte-identical, as a real process restart would be), so the
    in-memory tier starts empty and only the disk tier can help.
    Asserts the acceptance invariant: with a warm cache, the restart
    and the serving warm-up ladder compile ZERO programs live."""
    import shutil
    import tempfile

    import paddle_tpu.fluid as fluid
    from paddle_tpu import inference
    from paddle_tpu.fluid import compile_cache, layers, monitor, unique_name
    from paddle_tpu.inference import ServeConfig, Server

    cache_dir = tempfile.mkdtemp(prefix="bench_restart_cache_")
    model_dir = tempfile.mkdtemp(prefix="bench_restart_model_")
    env_prev = os.environ.get(compile_cache.ENV_DIR)
    os.environ[compile_cache.ENV_DIR] = cache_dir

    def hits_misses():
        return (
            monitor.counter("executor_compile_cache_disk_hit_total").value,
            monitor.counter("executor_compile_cache_disk_miss_total").value)

    def build_train():
        main, startup = fluid.Program(), fluid.Program()
        with unique_name.guard(), fluid.program_guard(main, startup):
            x = layers.data("x", shape=[64], dtype="float32")
            y = layers.data("y", shape=[1], dtype="float32")
            h = x
            for _ in range(4):
                h = layers.fc(h, 256, act="relu")
            loss = layers.reduce_mean(
                layers.square_error_cost(layers.fc(h, 1), y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(32, 64).astype(np.float32),
            "y": rng.rand(32, 1).astype(np.float32)}

    def one_restart():
        """Build + init + first step: the whole downtime window a
        respawned worker pays before training resumes."""
        t0 = time.perf_counter()
        main, startup, loss = build_train()
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
            lv = float(np.asarray(lv))
        return time.perf_counter() - t0, lv

    try:
        h0, m0 = hits_misses()
        t_cold, loss_cold = one_restart()
        h1, m1 = hits_misses()
        t_warm, loss_warm = one_restart()
        h2, m2 = hits_misses()
        assert m1 - m0 == 2 and h1 == h0, (
            "cold restart: want 2 disk misses (startup+main), "
            "got %d misses / %d hits" % (m1 - m0, h1 - h0))
        assert h2 - h1 == 2 and m2 == m1, (
            "warm restart compiled live: %d hits / %d misses "
            "(want 2 / 0)" % (h2 - h1, m2 - m1))
        assert loss_warm == loss_cold, (
            "deserialized executable diverged: %r vs %r"
            % (loss_cold, loss_warm))

        # serving cold-start: save a model once, then register it on
        # two fresh Servers — the second warm-up ladder must be served
        # entirely from disk
        smain, sstartup = fluid.Program(), fluid.Program()
        with unique_name.guard(), fluid.program_guard(smain, sstartup):
            x = layers.data("x", shape=[32], dtype="float32")
            prob = layers.softmax(layers.fc(layers.fc(
                x, 64, act="relu"), 8))
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            exe.run(sstartup)
            fluid.io.save_inference_model(model_dir, ["x"], [prob], exe,
                                          main_program=smain)
        cfg = ServeConfig(max_batch_size=8)
        ladder = len(cfg.ladder())
        exemplar = {"x": np.zeros((1, 32), np.float32)}

        def one_register():
            pred = inference.create_predictor(inference.Config(model_dir))
            t0 = time.perf_counter()
            with Server() as srv:
                srv.register("m", pred, config=cfg, warmup_feed=exemplar)
                return time.perf_counter() - t0

        h0, m0 = hits_misses()
        t_serve_cold = one_register()
        h1, m1 = hits_misses()
        t_serve_warm = one_register()
        h2, m2 = hits_misses()
        assert m1 - m0 == ladder and h1 == h0, (
            "cold register: want %d disk misses, got %d misses / %d "
            "hits" % (ladder, m1 - m0, h1 - h0))
        assert h2 - h1 == ladder and m2 == m1, (
            "warm register compiled live: %d hits / %d misses "
            "(want %d / 0)" % (h2 - h1, m2 - m1, ladder))
    finally:
        if env_prev is None:
            os.environ.pop(compile_cache.ENV_DIR, None)
        else:
            os.environ[compile_cache.ENV_DIR] = env_prev
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(model_dir, ignore_errors=True)

    load_hist = monitor.get_metric("compile_cache_load_seconds")
    return {
        "restart_cold_to_first_step_seconds": round(t_cold, 3),
        "restart_warm_to_first_step_seconds": round(t_warm, 3),
        "restart_speedup": round(t_cold / max(t_warm, 1e-9), 3),
        "restart_register_cold_seconds": round(t_serve_cold, 3),
        "restart_register_warm_seconds": round(t_serve_warm, 3),
        "restart_register_speedup":
            round(t_serve_cold / max(t_serve_warm, 1e-9), 3),
        "restart_ladder_size": ladder,
        "restart_cache_load_seconds_sum": round(load_hist.sum, 3)
        if load_hist is not None else 0.0,
    }


def monitor_summary():
    """Framework-counter sub-dict for the JSON line (fluid/monitor.py):
    the same counters a production scrape would see, so BENCH_r0x.json
    captures executor/compile-cache behavior alongside throughput."""
    from paddle_tpu.fluid import monitor

    hits = monitor.counter("executor_compile_cache_hit_total").value
    misses = monitor.counter("executor_compile_cache_miss_total").value
    run_hist = monitor.get_metric("executor_run_seconds")
    fetch_hist = monitor.get_metric("executor_fetch_sync_seconds")
    dec_hist = monitor.get_metric("decode_step_seconds")
    dec_cache = monitor.get_metric("decode_cache_tokens")
    return {
        "executor_run_count": monitor.counter("executor_run_total").value,
        "compile_cache_hits": hits,
        "compile_cache_misses": misses,
        "compile_cache_hit_ratio": round(hits / max(1, hits + misses), 4),
        # persistent disk tier (fluid/compile_cache.py): restarts and
        # serving cold-starts that deserialized instead of compiling
        "compile_cache_disk_hits": monitor.counter(
            "executor_compile_cache_disk_hit_total").value,
        "compile_cache_disk_misses": monitor.counter(
            "executor_compile_cache_disk_miss_total").value,
        "compile_cache_quarantined": monitor.counter(
            "compile_cache_quarantined_total").value,
        "compile_cache_evicted": monitor.counter(
            "compile_cache_evicted_total").value,
        "executor_run_seconds_sum": round(run_hist.sum, 3)
        if run_hist is not None else 0.0,
        "batched_run_count":
            monitor.counter("executor_batched_run_total").value,
        "batched_iters_total":
            monitor.counter("executor_batched_iters_total").value,
        "fetch_sync_count": fetch_hist.count
        if fetch_hist is not None else 0,
        "fetch_sync_seconds_sum": round(fetch_hist.sum, 3)
        if fetch_hist is not None else 0.0,
        "window_overlap_hits":
            monitor.counter("executor_window_overlap_hit_total").value,
        "window_overlap_misses":
            monitor.counter("executor_window_overlap_miss_total").value,
        # decode fast path: steps climb, compile_cache_misses don't — the
        # "no per-token retrace" invariant is readable straight off the
        # JSON line
        "decode_steps_total":
            monitor.counter("decode_steps_total").value,
        "decode_cache_tokens": dec_cache.value
        if dec_cache is not None else 0.0,
        "decode_step_seconds_sum": round(dec_hist.sum, 3)
        if dec_hist is not None else 0.0,
        # long-context tier: ring hop count climbs once per traced ring
        # pass (n_shards - 1 each); the gauge holds the last traced
        # sequence-shard count
        "attn_ring_hops_total":
            monitor.counter("attn_ring_hops_total").value,
        "attn_seq_shards": monitor.gauge("attn_seq_shards").value,
        # serving tier: coalescing + admission across ALL hosted models
        # (the per-model labeled series stay in dump_prometheus)
        "serving_requests_total": _sum_labeled("serving_requests_total"),
        "serving_batches_total": _sum_labeled("serving_batches_total"),
        "serving_shed_total": _sum_labeled("serving_shed_total"),
        "decode_slot_joins_total":
            monitor.counter("decode_slot_join_total").value,
        "decode_slot_retires_total":
            monitor.counter("decode_slot_retire_total").value,
        "decode_slot_scatter_dispatches_total":
            monitor.counter("decode_slot_scatter_dispatch_total").value,
        # paged decode engine: page pool churn, prefix-cache behavior,
        # and the Pallas paged-kernel dispatch count (0 on the gather-
        # dense fallback path)
        "decode_pages_allocated_total":
            monitor.counter("decode_pages_allocated_total").value,
        "decode_pages_freed_total":
            monitor.counter("decode_pages_freed_total").value,
        "decode_pages_shared_total":
            monitor.counter("decode_pages_shared_total").value,
        "decode_prefix_hits_total":
            monitor.counter("decode_prefix_hit_total").value,
        "decode_prefix_misses_total":
            monitor.counter("decode_prefix_miss_total").value,
        "attn_paged_kernel_dispatches_total":
            monitor.counter("attn_kernel_dispatch_total",
                            labels={"tier": "paged"}).value,
        # speculative decoding: mean tokens emitted per target verify
        # dispatch (1.0 = speculation never helps; k = always accepts)
        "decode_spec_verify_steps":
            _hist_count("decode_spec_accepted_tokens"),
        "decode_spec_accepted_tokens_total":
            _hist_sum("decode_spec_accepted_tokens"),
        "decode_spec_accepted_per_step": _hist_mean(
            "decode_spec_accepted_tokens"),
        # sparse embedding engine: residency/prefetch behavior summed
        # across ALL tables (per-table labeled series stay in
        # dump_prometheus)
        "embedding_prefetch_hit_total":
            _sum_labeled("embedding_prefetch_hit_total"),
        "embedding_prefetch_miss_total":
            _sum_labeled("embedding_prefetch_miss_total"),
        "embedding_evictions_total":
            _sum_labeled("embedding_evictions_total"),
        # telemetry plane state, so a BENCH_SERVE/BENCH_FLEET p50 in the
        # JSON history is comparable against runs with tracing on/off
        # (the acceptance bar: default-sampled tracing within noise)
        "telemetry": _telemetry_summary(),
    }


def _telemetry_summary():
    from paddle_tpu import telemetry

    if not telemetry.enabled():
        return {"enabled": False}
    return {
        "enabled": True,
        "sample": float(os.environ.get(telemetry.ENV_SAMPLE, 1.0) or 1.0),
        "spans_recorded": len(telemetry.snapshot()),
        "spans_dropped": telemetry.dropped_span_count(),
    }


def _sum_labeled(name):
    """Sum a counter across every label set it was registered under."""
    from paddle_tpu.fluid import monitor

    return monitor.sum_labeled(name)


def _hist_count(name):
    from paddle_tpu.fluid import monitor

    h = monitor.get_metric(name)
    return h.count if h is not None else 0


def _hist_sum(name):
    from paddle_tpu.fluid import monitor

    h = monitor.get_metric(name)
    return round(h.sum, 3) if h is not None else 0.0


def _hist_mean(name):
    from paddle_tpu.fluid import monitor

    h = monitor.get_metric(name)
    if h is None or not h.count:
        return 0.0
    return round(h.sum / h.count, 3)


def bench_smoke():
    """``bench.py --smoke``: two tiny step-batched windows through the
    FULL async pipeline — py_reader feeds, background window prefetch,
    async fetch handles — on CPU in seconds, no TPU needed. Asserts the
    pipeline invariants (second window is an overlap hit, zero fetch
    syncs before ``.numpy()``, finite decoupled losses) and prints the
    same one-line JSON shape as the real bench."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if ("jax" not in sys.modules
            and "xla_force_host_platform_device_count" not in _flags):
        # the pipeline smoke leg wants a 2-stage mesh; harmless for the
        # rest (every other leg shards or replicates transparently)
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers, monitor

    monitor.reset()
    B, D, K = 8, 4, 2
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        reader = layers.py_reader(capacity=8, shapes=[[B, D], [B, 1]],
                                  dtypes=["float32", "float32"])
        x, y = layers.read_file(reader)
        pred = layers.fc(x, 1, name="smoke_fc")
        loss = layers.reduce_mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    batches = [(rng.rand(B, D).astype(np.float32),
                rng.rand(B, 1).astype(np.float32)) for _ in range(2 * K)]
    reader.decorate_tensor_provider(lambda: iter(batches))
    exe = fluid.Executor()
    t0 = time.perf_counter()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        reader.start()
        handles = []
        for _ in range(2):
            (h,) = exe.run(main, fetch_list=[loss], iters=K,
                           fetch_mode="async", prefetch=True)
            handles.append(h)
        syncs_before = monitor.get_metric(
            "executor_fetch_sync_seconds").count
        losses = [h.numpy().ravel().tolist() for h in handles]
    exe.close()
    assert syncs_before == 0, (
        "async windows synced %d time(s) before .numpy()" % syncs_before)
    assert all(np.isfinite(np.asarray(l)).all() for l in losses), losses
    hits = monitor.counter("executor_window_overlap_hit_total").value
    assert hits >= 1, "window 2 did not consume the prefetched window"

    # tiny KV-cache decode loop (CPU): the (prefill, decode) pair must
    # compile exactly twice and a repeat generation must not retrace —
    # the fast path can't silently rot out of --smoke coverage
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.models import transformer

    with dygraph.guard():
        model = transformer.Transformer.tiny()
        sess = transformer.build_decode_session(
            model, batch_size=2, src_len=6, prompt_len=4,
            cache_capacity=16, end_id=1)
        rng = np.random.RandomState(1)
        src = rng.randint(2, 512, (2, 6)).astype(np.int64)
        prompt = rng.randint(2, 512, (2, 4)).astype(np.int64)
        plens = np.array([4, 3], np.int64)
        m0 = monitor.counter("executor_compile_cache_miss_total").value
        toks, _ = sess.generate(src, prompt, plens, 6)
        m1 = monitor.counter("executor_compile_cache_miss_total").value
        toks2, _ = sess.generate(src, prompt, plens, 6)
        m2 = monitor.counter("executor_compile_cache_miss_total").value

        # speculative smoke: a full-depth self-draft over the same
        # session must cost exactly two extra compiles (draft + verify)
        # and reproduce the baseline tokens bit-for-bit
        spec_hist = monitor.get_metric("decode_spec_accepted_tokens")
        sc0, ss0 = spec_hist.count, spec_hist.sum
        spec = transformer.build_speculative_session(
            model, sess, k=3, draft_layers=len(model.dec_layers))
        spec_toks, _ = spec.generate(src, prompt, plens, 6)
        m3 = monitor.counter("executor_compile_cache_miss_total").value
        spec_acc = (spec_hist.sum - ss0) / max(1, spec_hist.count - sc0)

        # paged smoke: the block-pool engine through join/step must
        # cost exactly two compiles (batch-1 prefill + paged decode)
        # and emit the dense baseline's tokens per slot
        paged = transformer.build_paged_decode_session(
            model, batch_size=2, src_len=6, prompt_len=4,
            cache_capacity=16, end_id=1, page_tokens=4)
        paged_done = {}
        for b in range(2):
            pslot, pdone = paged.join(src[b], prompt[b],
                                      prompt_len=int(plens[b]),
                                      max_new_tokens=6)
            if pdone is not None:
                paged_done[pslot] = pdone[0]
        while paged.active_count:
            for pslot, ptoks, _pfin in paged.step():
                paged_done[pslot] = ptoks
        m4 = monitor.counter("executor_compile_cache_miss_total").value
    assert m1 - m0 == 2, "decode smoke: %d compiles, want 2" % (m1 - m0)
    assert m2 == m1, "decode smoke: repeat generation retraced"
    assert (toks == toks2).all(), "decode smoke: non-deterministic"
    assert m3 - m2 == 2, (
        "spec smoke: %d compiles, want 2 (draft + verify)" % (m3 - m2))
    assert (spec_toks == toks).all(), (
        "spec smoke: speculative tokens diverged from dense baseline")
    assert m4 - m3 == 2, (
        "paged smoke: %d compiles, want 2 (prefill1 + paged decode)"
        % (m4 - m3))
    for b in range(2):
        _pt = np.asarray(paged_done[b])
        assert np.array_equal(_pt, toks[b][:_pt.size]), (
            "paged smoke: slot %d tokens diverged from dense" % b)

    # tiny embedding loop: DeepFM with its big table host-offloaded at a
    # budget far under the vocabulary — admissions, evictions, and the
    # prefetch overlap path must all fire on CPU in a couple of seconds
    from paddle_tpu import embedding
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.models import deepfm

    embedding.reset_tables()
    try:
        ecfg = deepfm.DeepFMConfig(sparse_feature_dim=640, num_fields=4,
                                   num_dense=3, embedding_size=4,
                                   fc_sizes=(16,))
        embedding.HostEmbeddingTable(
            "fm_emb", num_rows=ecfg.sparse_feature_dim,
            dim=ecfg.embedding_size, resident_budget=64, seed=7)
        with unique_name.guard():
            emain, estartup, eloss, _ = deepfm.build_train_program(
                ecfg, residence="host")
        eexe = fluid.Executor()
        feeds = [deepfm.synthetic_batch(ecfg, 8, seed=i) for i in range(5)]
        with fluid.scope_guard(fluid.Scope()):
            eexe.run(estartup)
            embed_losses = []
            for i, f in enumerate(feeds):
                (lv,) = eexe.run(emain, feed=f, fetch_list=[eloss])
                embed_losses.append(float(np.asarray(lv)))
                if i + 1 < len(feeds):
                    embedding.prefetch(emain, feeds[i + 1])
        assert all(np.isfinite(embed_losses)), embed_losses
        embed_hits = _sum_labeled("embedding_prefetch_hit_total")
        embed_evictions = _sum_labeled("embedding_evictions_total")
        assert embed_hits > 0, "embedding smoke: prefetch never hit"
        assert embed_evictions > 0, "embedding smoke: no evictions"
    finally:
        embedding.reset_tables()

    # tiny serving loop: 8 client threads through the dynamic batcher —
    # every future must resolve and the stream must coalesce
    serve = bench_serve(n_clients=8, per_client=2, max_batch_size=4,
                        max_queue_delay_ms=2.0, max_req_rows=2)
    assert serve["serve_batches"] < serve["serve_requests"], (
        "serve smoke: no coalescing happened")

    # tiny fleet loop: coord + one in-process replica + router + client
    # — registration via lease, routed traffic, graceful drain; the
    # serving-fleet wiring can't silently rot out of --smoke coverage
    import tempfile as _tf

    from paddle_tpu.distributed.coordination import CoordServer
    from paddle_tpu.serving import FleetClient, Replica, Router

    fleet_dir = _fleet_model_dir(_tf.mkdtemp(prefix="bench_smoke_fleet_"),
                                 prelower=False)
    fcoord = CoordServer().start()
    faddr = "%s:%d" % (fcoord.host, fcoord.port)
    frep = Replica(_fleet_spec(fleet_dir), coord_addr=faddr,
                   replica_id="smoke0", lease_ttl=5.0,
                   stats_interval=0.1).start()
    frouter = Router(coord_addr=faddr, refresh_interval=0.1).start()
    fleet_routed0 = _sum_labeled("fleet_routed_total")
    try:
        fcli = FleetClient("%s:%d" % (frouter.host, frouter.port))
        frng = np.random.RandomState(2)
        for _ in range(8):
            fx = frng.rand(frng.randint(1, 5), 32).astype(np.float32)
            fout = fcli.submit("fc", {"x": fx}, deadline_ms=10000)
            assert fout[0].shape == (fx.shape[0], 8)
        fcli.close()
    finally:
        frouter.close()
        frep.drain(timeout=10)
        fcoord.stop()
    fleet_routed = _sum_labeled("fleet_routed_total") - fleet_routed0
    assert fleet_routed == 8, (
        "fleet smoke: %d/8 requests routed" % fleet_routed)

    # coordinator crash + recovery under fleet load (tiny closed loop,
    # same model dir): zero requests lost, stale routing observed, WAL
    # replay brings the same port back at a bumped epoch
    coordrec = bench_coord_recovery(smoke=True, model_dir=fleet_dir)

    # persistent compile cache: a warm "restart" (fresh Executor,
    # rebuilt program, same cache dir) must deserialize BOTH programs
    # from disk and compile zero live — the restart fast path can't
    # silently rot out of --smoke coverage
    import shutil
    import tempfile

    from paddle_tpu.fluid import compile_cache

    cache_tmp = tempfile.mkdtemp(prefix="bench_smoke_cache_")
    cache_env_prev = os.environ.get(compile_cache.ENV_DIR)
    os.environ[compile_cache.ENV_DIR] = cache_tmp
    try:
        def _cc_restart():
            cmain, cstartup = fluid.Program(), fluid.Program()
            with unique_name.guard(), fluid.program_guard(cmain, cstartup):
                cx = layers.data("x", shape=[D], dtype="float32")
                cy = layers.data("y", shape=[1], dtype="float32")
                closs = layers.reduce_mean(layers.square_error_cost(
                    layers.fc(cx, 1, name="cc_fc"), cy))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(closs)
            cexe = fluid.Executor()
            with fluid.scope_guard(fluid.Scope()):
                cexe.run(cstartup)
                (clv,) = cexe.run(cmain, feed={"x": batches[0][0],
                                               "y": batches[0][1]},
                                  fetch_list=[closs])
                return float(np.asarray(clv))

        def _cc_counters():
            return (monitor.counter(
                        "executor_compile_cache_disk_hit_total").value,
                    monitor.counter(
                        "executor_compile_cache_disk_miss_total").value)

        ch0, cm0 = _cc_counters()
        cc_cold = _cc_restart()
        ch1, cm1 = _cc_counters()
        cc_warm = _cc_restart()
        ch2, cm2 = _cc_counters()
        assert cm1 - cm0 == 2 and ch1 == ch0, (
            "cache smoke cold: %d misses / %d hits, want 2 / 0"
            % (cm1 - cm0, ch1 - ch0))
        assert ch2 - ch1 == 2 and cm2 == cm1, (
            "cache smoke warm restart compiled live: %d hits / %d "
            "misses, want 2 / 0" % (ch2 - ch1, cm2 - cm1))
        assert cc_warm == cc_cold, (
            "cache smoke: deserialized executable diverged")
    finally:
        if cache_env_prev is None:
            os.environ.pop(compile_cache.ENV_DIR, None)
        else:
            os.environ[compile_cache.ENV_DIR] = cache_env_prev
        shutil.rmtree(cache_tmp, ignore_errors=True)

    # tiny 2-stage GPipe pipeline: one step through with_pipeline must
    # populate the schedule-shape gauge and the microbatch counter (the
    # 3D-parallelism observability contract — BENCH_PIPELINE=1 runs the
    # full bubble/weak-scaling leg)
    import jax as _jax

    pipe_stages = 2 if len(_jax.devices()) >= 2 else 1
    pipe_mb0 = monitor.counter("pipeline_microbatches_total").value
    ptraced, pstartup, ploss, pcompiled, pfeed_fn = _build_tower_pipeline(
        n_layers=2, n_stages=pipe_stages, trace_batch=2, seq_len=8,
        vocab=64, d_model=32, n_heads=2, d_inner=64, num_microbatches=2)
    pexe = fluid.Executor()
    with fluid.scope_guard(ptraced._scope):
        pexe.run(pstartup)
        (plv,) = pexe.run(pcompiled, feed=pfeed_fn(4), fetch_list=[ploss])
    assert np.isfinite(np.asarray(plv)).all()
    pipe_bubble = monitor.gauge("pipeline_bubble_fraction").value
    pipe_mb = monitor.counter("pipeline_microbatches_total").value - pipe_mb0
    assert pipe_bubble == (pipe_stages - 1) / (2 + pipe_stages - 1), (
        "pipeline smoke: bubble gauge %r != analytic" % pipe_bubble)
    assert pipe_mb == 2, (
        "pipeline smoke: microbatch counter moved %d, want 2" % pipe_mb)

    return {
        "serve_smoke_requests_per_sec": serve["serve_requests_per_sec"],
        "serve_smoke_mean_batch_occupancy":
            serve["serve_mean_batch_occupancy"],
        "metric": "smoke_async_pipeline_seconds",
        "value": round(time.perf_counter() - t0, 3),
        "unit": "seconds",
        "vs_baseline": None,
        "windows": 2,
        "iters_per_window": K,
        "window_losses": losses,
        "decode_smoke_tokens": int(toks.size),
        "decode_smoke_compile_misses": int(m1 - m0),
        "decode_spec_smoke_compile_misses": int(m3 - m2),
        "decode_spec_smoke_accepted_per_step": round(spec_acc, 2),
        "decode_paged_smoke_compile_misses": int(m4 - m3),
        "embed_smoke_steps": len(embed_losses),
        "embed_smoke_prefetch_hits": embed_hits,
        "embed_smoke_evictions": embed_evictions,
        "cache_smoke_disk_hits": int(ch2 - ch1),
        "cache_smoke_disk_misses": int(cm1 - cm0),
        "fleet_smoke_routed": fleet_routed,
        "coord_smoke_requests_lost": coordrec["coord_requests_lost"],
        "coord_smoke_stale_routed": coordrec["coord_stale_routed"],
        "coord_smoke_recovery_s": coordrec["coord_recovery_s"],
        "pipeline_smoke_bubble_fraction": pipe_bubble,
        "pipeline_smoke_microbatches": pipe_mb,
        "monitor": monitor_summary(),
    }


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        print(json.dumps(bench_smoke()))
        sys.exit(0)
    import jax

    from paddle_tpu.fluid import compile_cache

    _dev = jax.devices()[0]
    if _dev.platform != "tpu":
        sys.exit("bench.py times a TPU and found platform=%s (%s): a "
                 "rate from this host would not be a device metric. "
                 "`--smoke` is the CPU leg."
                 % (_dev.platform, _dev.device_kind))
    compile_cache.use_jax_cache()
    r = bench_bert()
    assert r["mfu"] <= 1.0, (
        "MFU %.3f > 1: either the peak table is wrong for this chip or the "
        "timing missed work" % r["mfu"])
    out = {
        "metric": "bert_base_mlm_train_tokens_per_sec",
        "value": r["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,
    }
    out.update(r)
    if os.environ.get("BENCH_LENET") == "1":
        out.update(bench_lenet())
    if os.environ.get("BENCH_RESNET") == "1":
        out.update(bench_resnet())
    if os.environ.get("BENCH_DEEPFM") == "1":
        out.update(bench_deepfm())
    if os.environ.get("BENCH_TRANSFORMER") == "1":
        out.update(bench_transformer())
    if os.environ.get("BENCH_PIPELINE") == "1":
        out.update(bench_pipeline())
    if os.environ.get("BENCH_DECODE") == "1":
        out.update(bench_transformer_decode())
    if os.environ.get("BENCH_DECODE_PROFILE") == "1":
        out.update(bench_decode_profile())
    if os.environ.get("BENCH_SERVE") == "1":
        out.update(bench_serve())
    if os.environ.get("BENCH_FLEET") == "1":
        out.update(bench_fleet())
    if os.environ.get("BENCH_COORD") == "1":
        out.update(bench_coord_recovery())
    if os.environ.get("BENCH_EMBED") == "1":
        out.update(bench_embedding())
    if os.environ.get("BENCH_RESTART") == "1":
        out.update(bench_restart())
    if os.environ.get("BENCH_MULTIHOST") == "1":
        out.update(bench_multihost())
    if os.environ.get("BENCH_LONGCTX") == "1":
        out.update(bench_longctx())
    if os.environ.get("BENCH_LONGSEQ") == "1":
        out.update(bench_longseq())
        out.update(bench_longseq(batch_size=4, seq_len=4096,
                                 prefix="longseq4k"))
        out.update(bench_longseq(batch_size=2, seq_len=8192,
                                 prefix="longseq8k"))
    out["monitor"] = monitor_summary()
    print(json.dumps(out))
