"""Flash attention kernels (forward, dq, dkv) against their roofline:
the least time the window's attention needs over the summed device time
of the flash kernel operations in the trace, in percent.  None where the
trace has no flash kernel.

The least time is the larger of FLOPs over the chip's peak and HBM bytes
over its bandwidth, counted from the real segments and masks of every
attention site (the configuration's FLOPs counter names the sites),
whatever tiles a kernel visits:

* FLOPs: QK^T and PV forward, 2 * head_dim each per kept query-key pair
  and head, and 2.5 times that backward.
* Bytes: bf16 q, k, v, o read or written once forward; q, k, v, o, dO
  read and dQ, dK, dV written once backward, plus the float32 row
  statistics (one per query row and head, written forward, read
  backward).

The kernels have no name of their own: their operations carry the name
of the jitted wrapper, ``flash_attention_op``.
"""
import re

PATTERN = re.compile(r"flash_attention", re.I)


def site_work(n, heads, kv_heads, head_dim, causal, layers) -> tuple[float, float]:
    pairs = n * (n + 1) // 2 if causal else n * n
    flops = layers * 3.5 * 2 * 2 * heads * head_dim * pairs
    q = n * heads * head_dim * 2
    kv = n * kv_heads * head_dim * 2
    fwd_bytes = 2 * q + 2 * kv + 4 * n * heads
    bwd_bytes = 3 * q + 2 * kv + q + 2 * kv + 4 * n * heads
    return flops, layers * (fwd_bytes + bwd_bytes)


def work(examples, counter, model) -> tuple[float, float]:
    flops = nbytes = 0.0
    for ex in examples:
        for site in counter.attention_sites(ex, model):
            f, b = site_work(*site)
            flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def read(rec):
    t = rec.get("trace")
    if rec.get("kind") != "train" or not t:
        return None
    kernel_s = sum(s for name, s in t["kernels"].items() if PATTERN.search(name))
    if not kernel_s:
        return None
    flops, nbytes = work([ex for s in rec["steps"] for ex in s["examples"]],
                         rec["counter"], rec["model"])
    least, _ = rec["peak"].least_seconds(flops, nbytes)
    return 100.0 * least / (kernel_s * rec["chips"])
