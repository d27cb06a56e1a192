"""The attention kernels' share of their roofline: the least time the
chip could take for one step's attention (the larger of its FLOPs over
peak FLOP/s and its bytes over peak bytes/s, both from the family's
``attention_cost``) x steps / the summed device time of the Pallas
attention calls in the traced window.

The Pallas calls carry no name of their own yet (the trace calls them
``jvp__.N``, ``transpose_jvp___.N`` and ``step.N`` after the scope they were
traced in), so they are matched by kind: the device's
``custom-call`` operations with target ``tpu_custom_call``, in a step
whose only such calls they are. The driver has asserted from
``attn_kernel_dispatch_total`` which tiers the step contains, and where
it contains none this reads nothing."""

from trace_reduce import PALLAS_CALL


def reduce(run):
    trace = run["trace"]
    if trace is None or not run["kernel_tiers"] or run["peaks"] is None:
        return None
    seconds = trace["kind_seconds"].get(PALLAS_CALL)
    if not seconds:
        return None
    flops, nbytes = run["family"].attention_cost(run["cfg"], run["mix"])
    least = max(flops / run["peaks"]["flops_per_s"],
                nbytes / run["peaks"]["bytes_per_s"])
    return 100.0 * least * run["steps"] / seconds
