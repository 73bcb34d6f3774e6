"""Model FLOPs of the real work of a multimodal LLM: encoders with a
downsampling connector feeding a dense SwiGLU decoder.  A configuration
names this counter with ``"flops": "mllm"``; another model family adds
a file of its own beside it with the same three functions:

* ``llm_tokens(ex, model)``: the tokens the example trains the LLM on;
* ``train_flops(examples, model)``: model FLOPs of one training step;
* ``attention_sites(ex, model)``: one ``(tokens, heads, kv_heads,
  head_dim, causal, layers)`` per attention the example passes through,
  which the kernels' roofline readers count their least work from.

Everything is counted from real token and segment counts, never from
padded capacities.  A matmul of [m, k] by [k, n] is 2*m*k*n FLOPs; a
training step is the forward pass and a backward pass of twice its
FLOPs, except where no gradient flows into an input (the stub
embeddings entering the input projection: weight gradient only).
Recomputation (remat) is not counted.

Attention counts query-key pairs that the mask keeps: every pair of an
encoder example (bidirectional), the lower triangle with the diagonal
of an LLM example (causal).  QK^T and PV each take 2 * head_dim FLOPs
per pair and head; the backward takes QK^T recomputed aside, dV, dP,
dQ and dK: 2.5 times the forward.
"""
from __future__ import annotations


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _encoder_tokens(ex, e: dict) -> int:
    return ex.vision if e["name"] == "vision" else ex.audio


def llm_tokens(ex, model: dict) -> int:
    """Tokens the example puts into the LLM (after downsampling)."""
    ds = {e["name"]: e["downsample"] for e in model["encoders"]}
    return (ex.text + (_ceil_div(ex.vision, ds["vision"]) if ex.vision else 0)
            + (_ceil_div(ex.audio, ds["audio"]) if ex.audio else 0))


def supervised(ex) -> int:
    """Positions that carry a label: each text token but an example's
    first (its predecessor predicts it) -- one per text token that has
    a position before it."""
    return ex.text - (1 if ex.order[0] == "text" else 0)


def attn_pairs(n: int, causal: bool) -> int:
    return n * (n + 1) // 2 if causal else n * n


def attention_sites(ex, model: dict) -> list[tuple]:
    H = model["n_heads"]
    sites = [(llm_tokens(ex, model), H, model["n_kv_heads"], model["d_model"] // H,
              True, model["n_layers"])]
    for e in model["encoders"]:
        n = _encoder_tokens(ex, e)
        if n:
            sites.append((n, e["n_heads"], e["n_heads"], e["d_model"] // e["n_heads"],
                          False, e["n_layers"]))
    return sites


def attention_fwd_flops(n: int, heads: int, head_dim: int, causal: bool) -> float:
    return 2 * 2 * heads * head_dim * attn_pairs(n, causal)


def encoder_fwd_flops(e: dict, n: int, d_llm: int) -> tuple[float, float]:
    """Forward FLOPs of encoder ``e`` and its connector on n tokens,
    split into (weight matmuls, input projection)."""
    De, Fe, L = e["d_model"], e["d_ff"], e["n_layers"]
    dense = L * 2 * n * (4 * De * De + 2 * De * Fe)
    rows = _ceil_div(n, e["downsample"])
    dense += 2 * rows * (De * e["downsample"] * d_llm + d_llm * d_llm)
    proj = 2 * n * e["embed_dim"] * De
    return dense, proj


def llm_fwd_flops(model: dict, L: int, labels: int) -> float:
    """Weight matmuls, the LM head on labelled rows included."""
    D, F, H, Hkv = model["d_model"], model["d_ff"], model["n_heads"], model["n_kv_heads"]
    hd = D // H
    per_tok = 2 * (D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F)
    return model["n_layers"] * L * per_tok + 2 * labels * D * model["vocab_size"]


def train_flops(examples, model: dict) -> float:
    """Model FLOPs of one training step over these examples."""
    total = 0.0
    for ex in examples:
        total += 3 * llm_fwd_flops(model, llm_tokens(ex, model), supervised(ex))
        for e in model["encoders"]:
            n = _encoder_tokens(ex, e)
            if n:
                dense, proj = encoder_fwd_flops(e, n, model["d_model"])
                total += 3 * dense + 2 * proj
        for n, heads, _, hd, causal, layers in attention_sites(ex, model):
            total += 3.5 * layers * attention_fwd_flops(n, heads, hd, causal)
    return total
