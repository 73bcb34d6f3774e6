"""Share of the traced training window in which a collective (the
communicator's all-to-all exchange, the gradient all-reduce and the
other all-gathers, reduce-scatters and permutes) ran on a chip while
no other operation did, averaged over chips, in percent.  None where
the window ran no collective (one chip)."""


def read(rec):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
