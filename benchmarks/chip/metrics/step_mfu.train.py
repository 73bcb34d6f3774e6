"""The whole training step's share of the chip's peak while the device
is busy: model FLOPs of the real work of the window's steps
(the configuration's FLOPs counter) over the device-busy seconds of the traced window
x chips x peak, in percent.  It bounds every kernel's roofline share
that moves train_mfu."""


def read(rec):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t or not rec["steps"]:
        return None
    work = sum(s["flops"] for s in rec["steps"])
    return 100.0 * work / (t["busy_s"] * rec["chips"] * rec["peak"].flops)
