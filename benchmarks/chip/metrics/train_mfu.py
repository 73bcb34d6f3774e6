"""Model FLOPs of the real work of every step completed in the window
(the configuration's FLOPs counter, ``flops/<name>.py``: encoders,
connector, LLM and LM head, forward and backward, over the tokens each
really saw; no recomputation), over window x chips x the chip's peak
(peaks.py), in percent."""


def read(rec):
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    work = sum(s["flops"] for s in rec["steps"])
    return 100.0 * work / (rec["window_s"] * rec["chips"] * rec["peak"].flops)
