#!/usr/bin/env python
"""Per-stage memory accounting for a candidate pipeline cut.

``PipelineOptimizer(cut_list=...)`` decides which ops land on which
stage rank; a bad cut starves some ranks and blows the memory budget of
others. This tool audits a candidate cut BEFORE committing devices to
it, using the exact segmentation the compiled schedule will run
(``fluid.compiler.pipeline_segments``) and the same static liveness
walk the long-context tier uses (``utils.liveness.peak_live_bytes``):

  * ``param_bytes``     — parameters consumed by the stage's forward
    ops. Gradients and optimizer slots live on the same rank, so the
    training-state footprint scales with this number.
  * ``peak_act_bytes``  — peak live bytes of the stage's forward
    segment at microbatch shape (born at the defining eqn, dead after
    the last use — an estimate of logical buffers, not an XLA
    allocation model; compare stages against each other).
  * ``boundary_bytes``  — the activation bundle ppermuted to the next
    stage each schedule tick.

Library use: ``stage_report(program, feed)`` with a feed dict at
MICROBATCH batch size. CLI (builds the demo EncoderTower LM):

  PYTHONPATH=. python tools/stagebalance.py --stages 2 --layers 4 \
      --mb-rows 4 --seq 32 [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def _var_nbytes(var):
    shape = [int(s) for s in var.shape]
    if any(s < 0 for s in shape):
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(var.dtype).itemsize


def stage_report(program, feed):
    """Audit the recorded pipeline cut of ``program``.

    ``feed``: {name: array} at MICROBATCH batch size (shapes/dtypes are
    what matter — nothing executes). Returns a list of per-stage dicts
    ``{stage, ops, param_bytes, peak_act_bytes, boundary_bytes}``.
    Raises ValueError when a non-cut var crosses a stage boundary — the
    same GPipe contract violation the compiled schedule would reject,
    surfaced with the leaking names.
    """
    import jax

    from paddle_tpu.fluid import rng as _rng
    from paddle_tpu.fluid.compiler import pipeline_segments
    from paddle_tpu.fluid.registry import LowerCtx, lower_op
    from paddle_tpu.utils.liveness import peak_live_bytes

    block = program.global_block()
    segments, cut_groups, _ = pipeline_segments(program, block)

    feed_sds = {n: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                        np.asarray(v).dtype)
                for n, v in feed.items()}

    def _is_param(name):
        try:
            v = block.var(name)
        except Exception:
            return False
        return bool(getattr(v, "persistable", False))

    report = []
    boundary_sds = {}   # incoming activations for the current stage
    for r, seg in enumerate(segments):
        produced = set()
        needed = []
        for op in seg:
            for nm in op.input_arg_names():
                if nm not in produced and nm not in needed:
                    needed.append(nm)
            produced.update(op.output_arg_names())

        params, env_tmpl, leaked = [], {}, []
        for nm in needed:
            if nm in boundary_sds:
                env_tmpl[nm] = boundary_sds[nm]
            elif nm in feed_sds:
                env_tmpl[nm] = feed_sds[nm]
            elif _is_param(nm):
                params.append(nm)
                v = block.var(nm)
                env_tmpl[nm] = jax.ShapeDtypeStruct(
                    tuple(int(s) for s in v.shape), np.dtype(v.dtype))
            else:
                leaked.append(nm)
        if leaked:
            raise ValueError(
                "stage %d consumes %r which earlier stages produce but "
                "the cut does not carry — add them to the cut bundle "
                "(PipelineOptimizer cut_list entries may be lists)"
                % (r, leaked))

        out_names = list(cut_groups[r]) if r < len(cut_groups) else [
            nm for op in seg for nm in op.output_arg_names()][-1:]

        def _seg_fn(env):
            ctx = LowerCtx(block, dict(env), _rng.root_key(0))
            for op in seg:
                lower_op(ctx, op)
            return [ctx.get(nm) for nm in out_names]

        closed = jax.make_jaxpr(_seg_fn)(env_tmpl)
        outs = jax.eval_shape(_seg_fn, env_tmpl)
        boundary_sds = dict(zip(out_names, outs))
        boundary_bytes = sum(
            int(np.prod(o.shape, dtype=np.int64)) * o.dtype.itemsize
            for o in outs) if r < len(cut_groups) else 0

        report.append({
            "stage": r,
            "ops": len(seg),
            "param_bytes": sum(_var_nbytes(block.var(nm)) for nm in params),
            "peak_act_bytes": int(peak_live_bytes(closed)),
            "boundary_bytes": int(boundary_bytes),
        })
    return report


def _build_demo(n_layers, n_stages, mb_rows, seq_len, vocab):
    """Tiny EncoderTower LM with uniform layer cuts."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import dygraph, layers, optimizer
    from paddle_tpu.models import transformer

    with dygraph.guard():
        model = transformer.EncoderTower(
            vocab, d_model=64, n_heads=4, d_inner=128, n_layers=n_layers,
            max_len=seq_len, dropout_rate=0.0)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, vocab, size=(mb_rows, seq_len)).astype("int64")
        pos = np.tile(np.arange(seq_len, dtype="int64"), (mb_rows, 1))
        args = [dygraph.to_variable(v) for v in (ids, pos)]
        _, traced = dygraph.jit.trace(model, args)
    startup = fluid.Program()
    with fluid.program_guard(traced.program, startup):
        blk = traced.program.global_block()
        logits = blk.var(traced._fetch_names[0])
        label = layers.data("sb_lbl", [seq_len, 1], dtype="int64")
        ce = layers.softmax_with_cross_entropy(
            layers.reshape(logits, [-1, vocab]),
            layers.reshape(label, [-1, 1]))
        loss = layers.mean(ce)
        opt = optimizer.SGD(learning_rate=0.1)
        if n_stages > 1:
            per = n_layers // n_stages
            cuts = [blk.var(model.last_checkpoints[per * (i + 1) - 1])
                    for i in range(n_stages - 1)]
            opt = optimizer.PipelineOptimizer(opt, cut_list=cuts)
        opt.minimize(loss)
    feed = dict(zip(traced._feed_names, (ids, pos)))
    feed["sb_lbl"] = rng.randint(0, vocab,
                                 size=(mb_rows, seq_len, 1)).astype("int64")
    return traced.program, feed


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="per-stage param/activation bytes for a pipeline cut")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--mb-rows", type=int, default=4,
                    help="microbatch rows (per-shard batch)")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.layers % args.stages:
        ap.error("--layers must divide evenly into --stages")
    program, feed = _build_demo(args.layers, args.stages, args.mb_rows,
                                args.seq, args.vocab)
    rows = stage_report(program, feed)
    if args.json:
        print(json.dumps(rows))
        return 0
    hdr = "%-6s %-5s %14s %16s %15s" % (
        "stage", "ops", "param_bytes", "peak_act_bytes", "boundary_bytes")
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        print("%-6d %-5d %14d %16d %15d" % (
            row["stage"], row["ops"], row["param_bytes"],
            row["peak_act_bytes"], row["boundary_bytes"]))
    pb = [r["param_bytes"] for r in rows]
    print("param imbalance (max/min): %.2f" % (max(pb) / max(min(pb), 1)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
