"""``readings.py`` for a cell whose state is most of the chip, with the
family's OWN planted faults besides, in one process on the chip at the
cell's own size:

    python benchmark/tools/readings_large.py --workload <cell> --seeds 201,202,... [--controls 2] [--faults 0]

``readings.py`` resets its step while the last seed's state is still in
the scope, and keeps the program's state beside the reference: two copies
of 7.5 GB of state do not fit. Here the scope is emptied before a reset
and before the reference comes, as the driver empties it, and the step is
gone before the controls come, whose references take their initial
weights from the host. For every seed: the program's first three
steps against the reference (the lower reading). Then, for the first
``--controls`` seeds, each in the reference put in the program's place: the control (fp8 matmuls), the fault
"half of the batch left out", and (unless ``--faults 0``) every fault the
family names in ``FAULTS`` (``reference_loss(cfg, mm, fault=name)``). One
JSON line a reading, with the limits it fails, on standard output.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def program_reading(step, family, driver, cfg, mix, seed):
    """``(what, numbers, where)``: the program's first three steps
    against the reference, the scope emptied before each comes."""
    import compare

    step.free()
    step.reset()
    step.set_params(compare.unstack(family.init_params(cfg, seed)))
    pool = family.feeds(cfg, mix, seed, compare.STEPS)
    got = driver.first_steps(step, family, cfg, seed, pool)
    step.free()
    ref = driver.reference(family, cfg, seed, pool)
    return ("program",) + compare.gaps(got, ref)


def reference_from_host(family, cfg, seed, pool, precision="f32",
                        half_batch=None, **planted):
    """``driver.reference`` with the initial weights handed over from the
    host: ``reference_steps`` then holds p, m, v and the gradient on the
    device and the kept initial copy off it until the last norm, which
    leaves the control's larger program the room it needs."""
    import jax

    import compare

    return compare.reference_steps(
        family.reference_loss(cfg, compare.matmul(precision), **planted),
        jax.device_get(family.init_params(cfg, seed)), pool,
        family.optimizer(cfg), half_batch=half_batch)


def control_readings(family, cfg, mix, seed, faults):
    """The control and the planted faults, each in the reference put in
    the program's place, against the sound reference."""
    import compare

    pool = family.feeds(cfg, mix, seed, compare.STEPS)
    ref = reference_from_host(family, cfg, seed, pool)
    yield ("control_fp8",) + compare.gaps(
        reference_from_host(family, cfg, seed, pool, "fp8"), ref)
    yield ("fault_half_batch",) + compare.gaps(
        reference_from_host(family, cfg, seed, pool,
                            half_batch=family.half_batch), ref)
    for fault in getattr(family, "FAULTS", ()) if faults else ():
        yield ("fault_" + fault,) + compare.gaps(
            reference_from_host(family, cfg, seed, pool, fault=fault), ref)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--faults", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"

    import jax

    import compare
    import run as harness

    _, cell, cfg, mix, limits, family, driver = harness.load_cell(
        args.workload)
    if args.rehearse_cpu:
        cfg, mix = family.tiny(cfg, mix)
        limits = limits["rehearse"]
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
        from paddle_tpu.fluid import compile_cache

        compile_cache.use_jax_cache()

    def say(seed, what, num, where):
        print(json.dumps({
            "cell": cell["name"], "seed": seed, "what": what,
            "numbers": num, "where": where,
            "fails": [k for k, x, lim in compare.judge(num, limits)[1]
                      if not x <= lim],
            "device": jax.devices()[0].device_kind}), flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    step = family.build(cfg, mix)
    for seed in seeds:
        say(seed, *program_reading(step, family, driver, cfg, mix, seed))
    # the step goes, and its executable's hold on the device with it,
    # before the controls' larger references come
    step.free()
    del step
    for seed in seeds[:args.controls]:
        for reading in control_readings(family, cfg, mix, seed,
                                        args.faults):
            say(seed, *reading)

if __name__ == "__main__":
    main()
