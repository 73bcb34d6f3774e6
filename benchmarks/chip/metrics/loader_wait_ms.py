"""Host time the training loop waited in next(loader) for the post-
balanced batch (sampling, dispatcher solve, packing), mean per window
step."""


def read(rec):
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    return sum(s["loader_wait_ms"] for s in rec["steps"]) / len(rec["steps"])
