"""Model assembly: parameter init, train/prefill forward, loss.

The forward contract consumes POST-BALANCED batches: per-DP-shard packed
token streams plus (for multimodal archs) per-encoder packed embedding
streams and the orchestrator's composed rearrangement plan (paper S6).

Batch keys (all leading dim S = total DP shards):
  tokens      [S, cap_T]  int32   packed text tokens
  labels      [S, cap_T]  int32   next-token targets; -1 = no loss
  text_dst    [S, cap_T]  int32   slot in the interleaved LLM stream
                                  (cap_L = dropped/padding)
  llm_seg     [S, cap_L]  int32   segment ids of the interleaved stream
  llm_pos     [S, cap_L]  int32   positions (restart per example)
  per encoder <e> (vlm / mllm families):
    enc_<e>_embeds [S, cap_E, embed_dim]   stub frontend output
    enc_<e>_seg/pos [S, cap_E]
    enc_<e>_plan_*  communicator arrays (composed Pi_M o Pi_E^-1)
    enc_<e>_dst  [S, cap_Eo] int32         slot in LLM stream after exchange
  audio (enc-dec) family:
    enc_embeds/enc_seg/enc_pos             encoder stream (stays separate,
                                           exchanged to the decoder's shard)
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import EncoderConfig, ModelConfig
from repro.models.layers import init_dense, layer_norm, rms_norm
from repro.obs.spans import phase
from repro.models.transformer import (
    cross_decoder_stack,
    decoder_stack,
    encoder_stack,
)

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ----------------------------------------------------------------------
# Init.
# ----------------------------------------------------------------------
def _init_attn(key, cfg: ModelConfig, L, D, dt) -> Params:
    hd, H, Hkv = cfg.head_dim_, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": init_dense(ks[0], (L, D, H * hd), dt),
        "wk": init_dense(ks[1], (L, D, Hkv * hd), dt),
        "wv": init_dense(ks[2], (L, D, Hkv * hd), dt),
        "wo": init_dense(ks[3], (L, H * hd, D), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, hd), dt)
        p["k_norm"] = jnp.ones((L, hd), dt)
    return p


def _init_dense_mlp(key, L, D, F, dt) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": init_dense(ks[0], (L, D, F), dt),
        "w_up": init_dense(ks[1], (L, D, F), dt),
        "w_down": init_dense(ks[2], (L, F, D), dt),
    }


def _init_moe_mlp(key, cfg: ModelConfig, L, D, F, dt) -> Params:
    ks = jax.random.split(key, 4)
    E = cfg.n_experts
    return {
        "router": init_dense(ks[0], (L, D, E), jnp.float32),
        "w_gate": init_dense(ks[1], (L, E, D, F), dt),
        "w_up": init_dense(ks[2], (L, E, D, F), dt),
        "w_down": init_dense(ks[3], (L, E, F, D), dt),
    }


def _init_mamba1(key, cfg: ModelConfig, L, dt) -> Params:
    D, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    dt_rank = max(1, D // 16)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "norm": jnp.ones((L, D), dt),
        "in_proj": init_dense(ks[0], (L, D, 2 * di), dt),
        "conv_w": init_dense(ks[1], (L, K, di), dt, scale=0.5),
        "x_proj": init_dense(ks[2], (L, di, dt_rank + 2 * N), dt),
        "dt_proj": init_dense(ks[3], (L, dt_rank, di), dt),
        "dt_bias": jnp.zeros((L, di), dt),
        "A_log": jnp.tile(jnp.log(A)[None], (L, 1, 1)),
        "D": jnp.ones((L, di), jnp.float32),
        "out_proj": init_dense(ks[4], (L, di, D), dt),
    }


def _init_mamba2(key, cfg: ModelConfig, L, dt) -> Params:
    D, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    H = di // cfg.ssm_headdim
    ks = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((L, D), dt),
        "in_proj": init_dense(ks[0], (L, D, 2 * di + 2 * N + H), dt),
        "conv_w": init_dense(ks[1], (L, K, di), dt, scale=0.5),
        "dt_bias": jnp.zeros((L, H), dt),
        "A_log": jnp.zeros((L, H), jnp.float32),
        "D": jnp.ones((L, H), jnp.float32),
        "out_proj": init_dense(ks[2], (L, di, D), dt),
    }


def _init_block_norms(cfg: ModelConfig, L, D, dt) -> Params:
    if cfg.nonparametric_norm:
        return {}
    return {"attn_norm": jnp.ones((L, D), dt), "mlp_norm": jnp.ones((L, D), dt)}


def _init_encoder(key, e: EncoderConfig, d_llm: int, dt) -> Params:
    """Modality encoder transformer (paper submodule) + MLP connector."""
    ks = jax.random.split(key, 8)
    L, D, F = e.n_layers, e.d_model, e.d_ff
    p: Params = {
        "input_proj": init_dense(ks[0], (e.embed_dim, D), dt),
        # Connector (paper: MLPs universally).
        "conn_in": init_dense(ks[1], (D * e.downsample, d_llm), dt),
        "conn_out": init_dense(ks[2], (d_llm, d_llm), dt),
    }
    if L > 0:
        p["layers"] = {
            "attn_norm": jnp.ones((L, D), dt),
            "mlp_norm": jnp.ones((L, D), dt),
            "wq": init_dense(ks[3], (L, D, D), dt),
            "wk": init_dense(ks[4], (L, D, D), dt),
            "wv": init_dense(ks[5], (L, D, D), dt),
            "wo": init_dense(ks[6], (L, D, D), dt),
            # ViT/whisper-style GELU MLP (matches the "audio" forward path).
            "w_in": init_dense(ks[7], (L, D, F), dt),
            "w_out": init_dense(jax.random.fold_in(ks[7], 1), (L, F, D), dt),
        }
        p["final_norm"] = jnp.ones((D,), dt)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    keys = jax.random.split(key, 12)
    params: Params = {"embed": init_dense(keys[0], (V, D), dt, scale=1.0)}

    if cfg.family in ("dense", "moe", "vlm"):
        layers = _init_block_norms(cfg, L, D, dt)
        layers.update(_init_attn(keys[1], cfg, L, D, dt))
        if cfg.family == "moe":
            layers.update(_init_moe_mlp(keys[2], cfg, L, D, F, dt))
        else:
            layers.update(_init_dense_mlp(keys[2], L, D, F, dt))
        params["layers"] = layers
    elif cfg.family == "ssm":
        params["layers"] = _init_mamba1(keys[1], cfg, L, dt)
    elif cfg.family == "hybrid":
        params["layers"] = _init_mamba2(keys[1], cfg, L, dt)
        shared = {"attn_norm": jnp.ones((D,), dt), "mlp_norm": jnp.ones((D,), dt)}
        sa = _init_attn(keys[2], cfg, 1, D, dt)
        shared.update({k: v[0] for k, v in sa.items()})
        shared.update({k: v[0] for k, v in _init_dense_mlp(keys[3], 1, D, F, dt).items()})
        params["shared_attn"] = shared
    elif cfg.family == "audio":
        eL = cfg.encoder_layers
        enc = {"attn_norm": jnp.ones((eL, D), dt), "mlp_norm": jnp.ones((eL, D), dt)}
        enc.update(_init_attn(keys[1], cfg, eL, D, dt))
        enc.update({
            "w_in": init_dense(keys[2], (eL, D, F), dt),
            "w_out": init_dense(keys[3], (eL, F, D), dt),
        })
        params["enc_layers"] = enc
        dec = {
            "attn_norm": jnp.ones((L, D), dt),
            "cross_norm": jnp.ones((L, D), dt),
            "mlp_norm": jnp.ones((L, D), dt),
        }
        dec.update(_init_attn(keys[4], cfg, L, D, dt))
        xa = _init_attn(keys[5], cfg, L, D, dt)
        dec.update({"x" + k: v for k, v in xa.items() if k.startswith("w")})
        dec.update({
            "w_in": init_dense(keys[6], (L, D, F), dt),
            "w_out": init_dense(keys[7], (L, F, D), dt),
        })
        params["layers"] = dec
    else:
        raise ValueError(cfg.family)

    if not cfg.nonparametric_norm:
        params["final_norm"] = jnp.ones((D,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[8], (D, V), dt)

    for i, e in enumerate(cfg.encoders):
        if cfg.family == "audio":
            # Enc-dec: the encoder stack lives in the model itself
            # (enc_layers at d_model); only the frontend-stub projection
            # is per-encoder.
            params[f"encoder_{e.name}"] = {
                "input_proj": init_dense(keys[9 + i], (e.embed_dim, D), dt)
            }
        else:
            params[f"encoder_{e.name}"] = _init_encoder(keys[9 + i], e, D, dt)
    return params


# ----------------------------------------------------------------------
# Loss (chunked: never materializes [T, V] for the full stream).
# ----------------------------------------------------------------------
def chunked_xent(x, lm_head, labels, *, chunk: int = 2048, unroll: int = 1):
    """x [B,T,D], labels [B,T] (-1 = ignore) -> (sum_loss, n_valid).

    The chunk body is checkpointed: backward recomputes each chunk's
    logits instead of keeping [T, V] alive (HBM would not fit)."""
    B, T, D = x.shape
    n_chunks = -(-T // chunk)
    pad = n_chunks * chunk - T
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, n_chunks, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, inp):
        xs, ls = inp
        logits = jnp.einsum("bcd,dv->bcv", xs.astype(jnp.float32),
                            lm_head.astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(ls, 0)[..., None], axis=-1
        )[..., 0]
        valid = ls >= 0
        loss = jnp.where(valid, logz - gold, 0.0)
        s, n = carry
        return (s + loss.sum(), n + valid.sum()), None

    (s, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)), (xc, lc),
                             unroll=min(unroll, n_chunks))
    return s, n


# ----------------------------------------------------------------------
# Forward (training / prefill).
# ----------------------------------------------------------------------
def run_encoder(cfg_e: EncoderConfig, p: Params, embeds, seg, pos, *,
                base_cfg: ModelConfig):
    """Stub-frontend embeddings -> connector tokens in LLM space.

    Returns [S, cap_E // downsample, d_llm]."""
    x = jnp.einsum("ste,ed->std", embeds.astype(_dtype(base_cfg)), p["input_proj"])
    if cfg_e.n_layers > 0:
        enc_cfg = _encoder_model_cfg(cfg_e, base_cfg)
        x = encoder_stack(enc_cfg, {"enc_layers": p["layers"]}, x, seg, pos)
        x = rms_norm(x, p["final_norm"])
    ds = cfg_e.downsample
    S, T, D = x.shape
    x = x.reshape(S, T // ds, D * ds)
    x = jnp.einsum("std,de->ste", x, p["conn_in"])
    return jnp.einsum("ste,ef->stf", jax.nn.gelu(x), p["conn_out"])


def _encoder_model_cfg(e: EncoderConfig, base: ModelConfig) -> ModelConfig:
    import dataclasses

    return dataclasses.replace(
        base,
        family="audio",  # LayerNorm + GELU path
        n_layers=e.n_layers,
        scan_unroll=e.scan_unroll,
        d_model=e.d_model,
        n_heads=e.n_heads,
        n_kv_heads=e.n_heads,
        head_dim=None,
        d_ff=e.d_ff,
        qk_norm=False,
        sliding_window=None,
        nonparametric_norm=False,
    )


def forward(cfg: ModelConfig, params: Params, batch: dict[str, jnp.ndarray],
            *, exchange: Callable | None = None):
    """Returns (sum_loss, n_tokens, aux_loss).

    ``exchange(name, tokens)``: the orchestrator's communicator closure
    that moves encoder-output tokens to their destination shards
    (composed rearrangement); identity when running single-host tests.
    """
    dt = _dtype(cfg)
    tokens = batch["tokens"]
    S, cap_T = tokens.shape

    if cfg.family == "audio":
        return _forward_encdec(cfg, params, batch, exchange)

    if cfg.encoders:
        cap_L = batch["llm_seg"].shape[1]
        x = jnp.zeros((S, cap_L, cfg.d_model), dt)
        text_emb = jnp.take(params["embed"], tokens, axis=0)
        # Scatter text tokens into their interleaved slots (index cap_L drops).
        x = _scatter_tokens(x, batch["text_dst"], text_emb)
        for e in cfg.encoders:
            p_e = params[f"encoder_{e.name}"]
            with phase(f"encoder.{e.name}"):
                enc_tok = run_encoder(
                    e, p_e, batch[f"enc_{e.name}_embeds"],
                    batch[f"enc_{e.name}_seg"], batch[f"enc_{e.name}_pos"],
                    base_cfg=cfg,
                )
            if exchange is not None:
                with phase(f"exchange.{e.name}"):
                    enc_tok = exchange(e.name, enc_tok)
            x = _scatter_tokens(x, batch[f"enc_{e.name}_dst"], enc_tok)
        seg, pos = batch["llm_seg"], batch["llm_pos"]
        labels = batch["llm_labels"]
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
        seg, pos = batch["seg"], batch["pos"]
        labels = batch["labels"]

    with phase("llm"):
        x, aux = decoder_stack(cfg, params, x, seg, pos)
    return (*_lm_head_loss(cfg, params, x, labels), aux)


def _forward_encdec(cfg, params, batch, exchange):
    dt = _dtype(cfg)
    e = cfg.encoders[0]
    p_e = params[f"encoder_{e.name}"]
    # Frontend-stub embeddings -> encoder input space.
    enc_in = jnp.einsum("ste,ed->std", batch[f"enc_{e.name}_embeds"].astype(dt),
                        p_e["input_proj"])
    enc_seg, enc_pos = batch[f"enc_{e.name}_seg"], batch[f"enc_{e.name}_pos"]
    with phase(f"encoder.{e.name}"):
        enc_out = encoder_stack(cfg, {"enc_layers": params["enc_layers"]},
                                enc_in, enc_seg, enc_pos)
    if exchange is not None:
        with phase(f"exchange.{e.name}"):
            enc_out = exchange(e.name, enc_out)
        enc_seg = batch[f"enc_{e.name}_seg_out"]
        enc_pos = batch[f"enc_{e.name}_pos_out"]
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    with phase("llm"):
        x = cross_decoder_stack(cfg, params, x, batch["seg"], batch["pos"],
                                enc_out, enc_seg, enc_pos)
    return (*_lm_head_loss(cfg, params, x, batch["labels"]), jnp.float32(0.0))


def _lm_head_loss(cfg, params, x, labels):
    """Final norm, LM head and the chunked loss: (sum_loss, n_tokens)."""
    with phase("lm_head"):
        x = _final_norm(cfg, params, x)
        lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return chunked_xent(x, lm_head, labels, unroll=_xent_unroll(cfg))


def _xent_unroll(cfg):
    # Roofline mode: unrolled scans so cost_analysis counts every chunk.
    return 10**9 if cfg.attention_backend == "chunked_unrolled" else 1


def _final_norm(cfg, params, x):
    if cfg.nonparametric_norm:
        return layer_norm(x, None, None)
    if cfg.family == "audio":
        return layer_norm(x, params["final_norm"], None)
    return rms_norm(x, params["final_norm"])


def _scatter_tokens(x, dst, values):
    """x [S, cap_L, D]; dst [S, T] slots (cap_L = drop); values [S, T, D]."""
    S, cap_L, D = x.shape

    def one(xs, ds, vs):
        padded = jnp.concatenate([xs, jnp.zeros((1, D), xs.dtype)], axis=0)
        padded = padded.at[ds].set(vs.astype(xs.dtype), mode="drop")
        return padded[:cap_L]

    return jax.vmap(one)(x, dst, values)
