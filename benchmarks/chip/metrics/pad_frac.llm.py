"""Padding slots over LLM capacity slots, over all shards of every
window step, in percent: counted from the packed batch's llm_seg
(segment id 0 = padding)."""


def read(rec):
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    real = sum(s["llm_real"] for s in rec["steps"])
    slots = sum(s["llm_slots"] for s in rec["steps"])
    return 100.0 * (1.0 - real / slots)
