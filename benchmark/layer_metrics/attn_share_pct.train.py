"""The Pallas attention calls' device time / device busy time. The calls
are matched as ``attn_roofline.train`` matches them."""

from trace_reduce import PALLAS_CALL


def reduce(run):
    trace = run["trace"]
    if trace is None or not run["kernel_tiers"]:
        return None
    seconds = trace["kind_seconds"].get(PALLAS_CALL)
    return 100.0 * seconds / trace["busy_s"] if seconds else None
