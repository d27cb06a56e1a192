"""What every Pallas kernel file here shares: whether the kernels can run
(a TPU, or the interpreter asked for), the one ``pl.pallas_call`` of
the package, which names the kernel for the trace, and the mark a producer
puts on a value that recomputation is to keep (``keep_across_recompute``)."""

import contextlib
import os

import jax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

# the one name ``ops/autodiff.py``'s checkpointed replay saves by
RECOMPUTE_KEEP = "recompute_keep"
_segment_depth = [0]    # > 0 while a checkpointed segment is being traced


def interpret():
    """PADDLE_TPU_PALLAS_INTERPRET=1 runs the kernels through the pallas
    interpreter (CPU CI exercises the real kernel bodies). On a TPU it
    is an error, not a mode: a stray setting would leave the chip idle
    behind the interpreter and say nothing."""
    on = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "") == "1"
    if on and jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            "PADDLE_TPU_PALLAS_INTERPRET=1 on a tpu platform: the Pallas "
            "kernels would run interpreted instead of compiled; unset it")
    return on


def supports_pallas():
    return interpret() or jax.devices()[0].platform == "tpu"


def named_pallas_call(name, kernel, **kw):
    """``pl.pallas_call`` under ``name``, given twice: as ``name=`` and as
    a ``named_scope`` round the call. XLA names the custom call after the
    innermost scope it sits in, and under a transform the outermost scope
    reads ``jvp(<name>)``; with two, the inner one stays plain whatever
    the call was traced under. ``name=`` also goes into the Mosaic module,
    so a compilation cache keyed on the program without its debug info
    cannot serve the unnamed kernel in place of this one."""
    call = pl.pallas_call(kernel, name=name, interpret=interpret(), **kw)

    def named(*args):
        with jax.named_scope(name):
            return call(*args)

    return named


@contextlib.contextmanager
def recompute_segment():
    """Held by ``ops/autodiff.py`` while it traces a checkpointed segment
    (the segment's ops and their ``custom_vjp`` forward rules), so that a
    marking site knows that its value can be kept."""
    _segment_depth[0] += 1
    try:
        yield
    finally:
        _segment_depth[0] -= 1


def keep_across_recompute(x, what):
    """Mark ``x`` as a value that a recomputed segment keeps for its
    backward pass instead of making it again (``ops/autodiff.py``:
    ``jax.checkpoint`` under ``save_only_these_names(RECOMPUTE_KEEP)``).
    Outside a checkpointed segment ``x`` comes back as it is: a program
    without checkpoints lowers to the text it had before any producer
    marked anything.

    The rule a producer decides by: mark a value when a kernel or a
    multi-pass op made it and it is deterministic given the segment's
    inputs, and then by one of two tests. A value that XLA ops make again
    is marked where that costs well over 0.03 ms a MB held. The outputs of
    a kernel call are weighed TOGETHER: where the kept set is everything
    the replay wants of the call (its outputs used downstream and the
    residuals its backward reads), keeping it takes the whole call out of
    the replay, and the call's time is set against the step's free memory,
    not against a per-MB rule. Marked today, with what remaking costs (chip
    runs on a v5e): the select tier's ``o`` and row logsumexp, 17.4 ms for
    136 MB; ``sparse_index``'s byte mask, 10.6 ms for 268 MB; the flash
    tier's ``o`` and logsumexp, 8.96 ms for 135 MB; ``moe_experts``'
    dispatch plan (``moe_plan``: a layer's rows, spans and weight table,
    1-2.75 MB); ``moe_route``'s chosen scores and ids (``moe_route``: 1.45
    ms of ``top_k`` for 1.3 MB); the delta rule's ``o``, the state
    entering each 128-row group and the groups' inverses (``delta_rule``,
    the whole forward kernel: 13.10 ms of ``kda_chunk_fwd`` for 671 MB a
    layer at 16,384 positions and 32 heads of 128, 5.08 ms of
    ``gdn_chunk_fwd`` for 671 MB at 2 x 8192 - 0.0195 and 0.0076 ms a MB,
    under the first test). A projection's output costs far less a MB and
    is not marked.

    ``recompute_kept_bytes_total{what}`` counts the bytes, once a site
    traced inside a checkpointed segment (trace-time, not per step)."""
    if not _segment_depth[0]:
        return x
    from ..fluid import monitor

    monitor.counter(
        "recompute_kept_bytes_total",
        "bytes a checkpointed segment keeps for its backward pass instead "
        "of recomputing them, by producer (trace-time: once a site traced "
        "inside a segment, not per step)",
        labels={"what": what}).inc(x.size * x.dtype.itemsize)
    return checkpoint_name(x, RECOMPUTE_KEEP)
