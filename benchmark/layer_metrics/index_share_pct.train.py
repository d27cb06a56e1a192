"""The learned selection's share of the device's busy time: the traced
window's instructions filed under ``sparse_index`` (the indexer's scores,
the threshold and the mask; forward and recomputed) / busy time. The
indexer's three projections are plain ``mul`` ops and are not in it."""

from step_regions import region_seconds

INDEX_OPS = ("sparse_index",)


def reduce(run):
    seconds = region_seconds(run, INDEX_OPS)
    return 100.0 * seconds / run["trace"]["busy_s"] if seconds else None
