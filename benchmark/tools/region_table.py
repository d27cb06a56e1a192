"""One cell under the program's own profiler, on the chip:

    python benchmark/tools/region_table.py --workload <cell> [--seconds 2]

Builds the cell's step as the driver does, warms it up, then runs the
driver's window inside ``fluid.profiler.start_profiler(trace_dir=...)`` and
prints what ``stop_profiler`` reports: the host table and device time by
region (forward / backward / optimizer / unattributed, and by program op
type), which ``PERF.md`` section 5 quotes. Then it opens the same trace
with ``jax.profiler.ProfileData`` and says where the program's
``executor.*`` spans lie against the driver's ``dispatch`` spans. The
report is kept in ``chiprun_out/``. Not a measurement of speed: the window
is short and traced.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def nesting(profile, outer_name, prefix):
    """``(inside, outside, by name)``: how many host spans whose name
    starts with ``prefix`` lie inside a span named ``outer_name``."""
    import trace_reduce

    outer, inner = [], []
    for plane in profile.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == outer_name:
                        outer.append(span)
                    elif e.name.startswith(prefix):
                        inner.append(span)
    inside, by_name = 0, {}
    for name, a, b in inner:
        by_name[name] = by_name.get(name, 0) + 1
        inside += any(oa <= a and b <= ob for _, oa, ob in outer)
    return inside, len(inner) - inside, by_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"

    import jax

    import compare
    import run as harness

    _, cell, cfg, mix, _, family, driver = harness.load_cell(args.workload)
    if args.rehearse_cpu:
        cfg, mix = family.tiny(cfg, mix)
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
        from paddle_tpu.fluid import compile_cache

        compile_cache.use_jax_cache()
    from paddle_tpu.fluid import profiler

    step = family.build(cfg, mix)
    step.set_params(compare.unstack(family.init_params(cfg, args.seed)))
    pool = family.feeds(cfg, mix, args.seed, mix["feed_pool"])
    for i in range(compare.STEPS + mix["warmup_steps"]):
        lv = step.run(pool[i % len(pool)])
    jax.block_until_ready(lv)

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(HERE, ".trace", "region_" + cell["name"])
    profiler.reset_profiler()
    profiler.start_profiler(trace_dir=trace_dir)
    out = driver.window(step, pool, args.seconds, mix["fetch_every"])
    # the step's HLO text, which stop_profiler asks for too, timed alone
    t = time.perf_counter()
    for compiled in step.exe._cache.values():
        if getattr(compiled.fn, "__name__", "") == "train_step":
            compiled.hlo_text()
    hlo_s = time.perf_counter() - t
    t = time.perf_counter()
    report = profiler.stop_profiler(
        sorted_key="total", silent=True,
        profile_path=os.path.join(out_dir, "region_%s.txt" % cell["name"]))
    print(report)
    print("\nthe step's HLO text took %.1f s, then stop_profiler %.1f s "
          "(the trace read back, the text once more, the table)"
          % (hlo_s, time.perf_counter() - t))

    inside, outside, by_name = nesting(
        profiler._load_trace(trace_dir), "dispatch", "executor.")
    print("%d steps in %.2f s; executor.* spans in /host:CPU: %d inside a "
          "'dispatch' span, %d outside; %r"
          % (out["steps"], out["window_s"], inside, outside, by_name))


if __name__ == "__main__":
    main()
