"""The one command: one cell, one run, one line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it (see ``benchmark/README.md``); nothing here knows a cell.
Exits non-zero, printing no result, anywhere but on a ``tpu`` with the
chips the cell asks for; ``--rehearse-cpu`` runs the family's tiny preset
on the CPU under the Pallas interpreter and reports no device metric.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py``, imported by its file."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        sys.exit("benchmark: no %s %r (%s)" % (kind, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (kind, name.replace(".", "_").replace("-", "_")),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    sys.exit("benchmark: BENCHMARK.json has no %s %r" % (what, name))


def load_cell(name):
    """Everything ``BENCHMARK.json`` and the files it names say of a cell:
    ``(bench, cell, cfg, mix, limits, family, driver)``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], name, "workload")
    conf = by_name(bench["configs"], cell["config"], "config")
    cfg = load_json(ROOT, conf["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    limits = load_json(HERE, "limits", cell["name"] + ".json")
    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    return (bench, cell, cfg, mix, limits,
            load_module("families", cfg["family"]),
            load_module("drivers", mix["driver"]))


def metrics_of(bench, section, cell):
    """The section's metrics that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    bench, cell, cfg, mix, limits, family, driver = load_cell(args.workload)

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    import jax

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if args.rehearse_cpu:
        if dev.platform != "cpu":
            sys.exit("benchmark: --rehearse-cpu is for the CPU; found %s"
                     % dev.platform)
        cfg, mix = family.tiny(cfg, mix)
        limits = limits["rehearse"]     # the toy preset's own, CPU readings
        peaks = None
    else:
        found = []
        if dev.platform != "tpu":
            found.append("platform=%s (%s)" % (dev.platform, dev.device_kind))
        if n_dev < cell["chips"]:
            found.append("%d device(s), the cell asks for %d"
                         % (n_dev, cell["chips"]))
        if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET") is not None:
            found.append("PADDLE_TPU_PALLAS_INTERPRET is set")
        if found:
            sys.exit("benchmark: refusing to run: " + "; ".join(found))
        import peaks as peaks_table

        peaks = peaks_table.peaks_for(dev.device_kind)
        from paddle_tpu.fluid import compile_cache

        # one cache inside the checkout (or where JAX_COMPILATION_CACHE_DIR
        # says); the sub-second compiles are kept too, in this process only
        compile_cache.use_jax_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    ctx = {"t_start": T_START, "cell": cell["name"], "chips": cell["chips"],
           "cfg": cfg, "mix": mix, "family": family, "limits": limits,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "rehearsal": args.rehearse_cpu,
           "peaks": peaks,
           "trace_dir": os.path.join(HERE, ".trace", cell["name"])}
    run = driver.run(ctx)

    section = "per_layer" if args.trace else "end_to_end"
    kind = "layer_metrics" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, section, cell["name"]):
        value = load_module(kind, m["name"]).reduce(run)
        if value is not None and not (args.rehearse_cpu
                                      and m["source"] == "device_trace"):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev,
              "memory_peak_bytes": run["memory"].get("peak_bytes_in_use")}
    line = {"correct": run["correct"], "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device}
    if run.get("trace") is not None:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"][:10],
                             "idle_gaps": run["trace"]["idle_gaps"][:10]}
    if args.rehearse_cpu:
        line["rehearsal"] = True
    line["compared"] = {name: {"value": x, "limit": lim}
                        for name, x, lim in run["compared"]}
    sys.stdout.flush()
    for name, x, lim in run["compared"]:
        print("compared %s %.6g limit %s (%s)"
              % (name, x, lim, run["where"].get(name)), file=sys.stderr)
    print("correct %s" % run["correct"], file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
