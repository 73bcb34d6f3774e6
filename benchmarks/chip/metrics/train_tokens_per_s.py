"""Real LLM-input tokens (text, plus vision and audio tokens after the
connector's downsampling; never padding) of every step completed in
the window, over the window."""


def read(rec):
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    return sum(s["tokens"] for s in rec["steps"]) / rec["window_s"]
