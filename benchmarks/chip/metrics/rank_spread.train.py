"""Spread of device compute time across the data-parallel ranks of the
traced training window: 100 x (largest / mean - 1) of each chip's
non-collective busy time (``compute_s_by_chip``), in percent.  Every
rank runs the same step at the same capacities, so what differs is
data-dependent work; the slowest rank sets the step.  None on one
chip."""


def read(rec):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t:
        return None
    chips = t.get("compute_s_by_chip") or []
    if len(chips) < 2:
        return None
    mean = sum(chips) / len(chips)
    return 100.0 * (max(chips) / mean - 1.0)
