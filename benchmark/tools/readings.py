"""The readings that the limits in ``benchmark/limits/`` are set from, in
one process on the chip at the cell's own size:

    python benchmark/tools/readings.py --workload <cell> --seeds 101,102,... [--controls 3]

For every seed: the program's first three steps against the reference
(the lower reading). For the first ``--controls`` seeds besides: the
control (the reference with fp8 matmuls, put in the program's place) and
the planted fault "half of the batch left out" (in the reference put in
the program's place), each against the reference. A state left unchanged
reads ``delta_gap`` 1 by construction and needs no run. One JSON line a
reading, on standard output and in ``chiprun_out/``.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def readings(step, family, driver, cfg, mix, seed, controls):
    """``(what, numbers, where)`` for one seed: the program, and with
    ``controls`` the control and the half-batch fault besides."""
    import compare

    step.reset()
    step.set_params(compare.unstack(family.init_params(cfg, seed)))
    pool = family.feeds(cfg, mix, seed, compare.STEPS)
    got = driver.first_steps(step, family, cfg, seed, pool)
    ref = driver.reference(family, cfg, seed, pool)
    yield ("program",) + compare.gaps(got, ref)
    if controls:
        yield ("control_fp8",) + compare.gaps(
            driver.reference(family, cfg, seed, pool, "fp8"), ref)
        yield ("fault_half_batch",) + compare.gaps(
            driver.reference(family, cfg, seed, pool,
                             half_batch=family.half_batch), ref)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"

    import jax

    import run as harness

    _, cell, cfg, mix, _, family, driver = harness.load_cell(args.workload)
    if args.rehearse_cpu:
        cfg, mix = family.tiny(cfg, mix)
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
        from paddle_tpu.fluid import compile_cache

        compile_cache.use_jax_cache()

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    step = family.build(cfg, mix)
    with open(os.path.join(ROOT, "chiprun_out",
                           "readings_%s.jsonl" % cell["name"]), "a") as log:
        for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
            for what, num, where in readings(step, family, driver, cfg, mix,
                                             seed, n < args.controls):
                line = json.dumps({
                    "cell": cell["name"], "seed": seed, "what": what,
                    "numbers": num, "where": where,
                    "device": jax.devices()[0].device_kind})
                print(line, flush=True)
                log.write(line + "\n")
                log.flush()


if __name__ == "__main__":
    main()
