"""Driver ``train_loop``: a training cell. Set-up builds the family's
step, gives it weights from the seed, drives it through its first three
steps (the ones the reference follows) and a few more, then hands the
same object to the window. The window dispatches steps for ``--seconds``
(a traced one for the mix's ``trace_seconds`` at most) and closes on
``block_until_ready`` of the last loss; the loss comes to the host every
``fetch_every`` steps, as a training loop logs it.
"""

import os
import shutil
import sys
import time

import jax
import numpy as np

import compare
import trace_reduce


def _compile_misses():
    from paddle_tpu.fluid import monitor

    return monitor.counter("executor_compile_cache_miss_total").value


def _kernel_tiers():
    from paddle_tpu.fluid import monitor
    from paddle_tpu.kernels.attention import KERNEL_TIERS

    return {t: monitor.counter("attn_kernel_dispatch_total",
                               labels={"tier": t}).value
            for t in KERNEL_TIERS}


def _scalar(lv):
    """The loss on the host: waits for its step."""
    return float(np.asarray(lv).ravel()[0])


def first_steps(step, family, cfg, seed, pool):
    """The program's side of the comparison: its first three steps,
    through the window's own call and feed."""
    opt = family.optimizer(cfg)
    got = {"loss": []}
    for i in range(compare.STEPS):
        got["loss"].append(_scalar(step.run(pool[i])))
        if i == 0:
            scale = 1.0 / (1.0 - opt["beta1"])
            got["grad"] = {k: scale * v for k, v in compare.to_floats(
                compare.leaf_norms(step.first_moments())).items()}
    got["delta"] = compare.to_floats(compare.leaf_delta_norms(
        step.params(), compare.unstack(family.init_params(cfg, seed))))
    return got


def reference(family, cfg, seed, pool, precision="f32", half_batch=None):
    """The plain reference's three steps, from the same seed."""
    return compare.reference_steps(
        family.reference_loss(cfg, compare.matmul(precision)),
        family.init_params(cfg, seed), pool, family.optimizer(cfg),
        half_batch=half_batch)


def window(step, pool, seconds, fetch_every):
    """Dispatch steps until ``seconds`` have passed; all the work over
    all the time. Returns what the metrics read."""
    from jax.profiler import TraceAnnotation

    n_pool = len(pool)
    losses, dispatch_s, dispatch_calls, i = [], 0.0, 0, 0
    with TraceAnnotation("bench_window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("next_feed"):
                feed = pool[i % n_pool]
            with TraceAnnotation("dispatch"):
                t = time.perf_counter()
                lv = step.run(feed)
                now = time.perf_counter()
            i += 1
            if i % fetch_every == 0:
                with TraceAnnotation("fetch_loss"):
                    losses.append(_scalar(lv))
            else:
                dispatch_s += now - t
                dispatch_calls += 1
            if now - t0 >= seconds:
                break
        with TraceAnnotation("drain"):
            jax.block_until_ready(lv)
            t1 = time.perf_counter()
    losses.append(_scalar(lv))
    return {"window_s": t1 - t0, "steps": i, "losses": losses,
            "dispatch_s": dispatch_s, "dispatch_calls": dispatch_calls}


def run(ctx):
    family, cfg, mix, seed = (ctx["family"], ctx["cfg"], ctx["mix"],
                              ctx["seed"])
    tiers0 = _kernel_tiers()
    step = family.build(cfg, mix)
    step.set_params(compare.unstack(family.init_params(cfg, seed)))
    pool = family.feeds(cfg, mix, seed, mix["feed_pool"])
    got = first_steps(step, family, cfg, seed, pool)
    for i in range(mix["warmup_steps"]):
        lv = step.run(pool[(compare.STEPS + i) % len(pool)])
    _scalar(lv)                 # fetch path warm, device drained
    tiers = {t: n - tiers0[t] for t, n in _kernel_tiers().items()
             if n > tiers0[t]}
    want = set(family.expected_kernel_tiers(cfg, mix))
    assert set(tiers) == want, (
        "Pallas attention tiers traced %r, the cell expects %r"
        % (sorted(tiers), sorted(want)))

    tracing = ctx["trace"] and not ctx["rehearsal"]   # the CPU has no device plane
    if tracing:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the spans are TraceAnnotations
        jax.profiler.start_trace(ctx["trace_dir"], profiler_options=options)
    misses0 = _compile_misses()
    setup_s = time.perf_counter() - ctx["t_start"]
    # a traced window is short: ten seconds of a step with 8,000 operations
    # are 1.7 M events, 190 MB, and four minutes to read back
    seconds = min(ctx["seconds"], mix["trace_seconds"]) if tracing \
        else ctx["seconds"]
    out = window(step, pool, seconds, mix["fetch_every"])
    misses = _compile_misses() - misses0
    trace = None
    if tracing:
        jax.profiler.stop_trace()
        trace = trace_reduce.summarize(
            trace_reduce.load(ctx["trace_dir"]), chips=ctx["chips"])
        print("trace: %.1f MB on disk, removed after reading" % (sum(
            os.path.getsize(os.path.join(d, f))
            for d, _, fs in os.walk(ctx["trace_dir"]) for f in fs) / 1e6),
            file=sys.stderr)
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    memory = max((dict(d.memory_stats() or {})
                  for d in jax.devices()[:ctx["chips"]]),
                 key=lambda m: m.get("peak_bytes_in_use", 0))   # the fullest

    # the program's state goes before the reference comes
    step.free()
    del step
    ref = reference(family, cfg, seed, pool)
    num, where = compare.gaps(got, ref)
    correct, compared = compare.judge(num, ctx["limits"])
    finite = bool(np.isfinite(out["losses"]).all())
    if not finite:
        compared.append(["window_losses_not_finite", 1.0, 0.0])
    out.update({
        "correct": bool(correct and finite),
        "compared": compared, "where": where,
        "attempted": out["steps"], "failed": 0 if finite else out["steps"],
        "setup_s": setup_s, "compiles_in_window": misses,
        "tokens_per_step": family.tokens_per_step(cfg, mix),
        "flops_per_step": family.flops(cfg, mix),
        "kernel_tiers": tiers, "memory": memory, "trace": trace,
        "peaks": ctx["peaks"], "chips": ctx["chips"], "cfg": cfg,
        "mix": mix, "family": family})
    return out
