"""Plain reference of the multimodal model: encoders, connector, decoder
LLM, next-token loss and its gradients, one example at a time, in
float32 at full matmul precision.  Nothing here imports the program.

It states the model the configuration file describes:

* each encoder (ViT / Whisper stand-in): stub embeddings [n, embed_dim]
  -> input projection -> pre-LayerNorm blocks (eps 1e-5, scale, no
  bias; bidirectional multi-head attention with rotary positions 0..n-1;
  GELU (tanh) MLP) -> RMSNorm (eps 1e-6) -> downsample by concatenating
  ``downsample`` neighbouring rows (a missing last row is zero) ->
  connector GELU MLP into the LLM width;
* the LLM: the example's subsequences in its interleave order (text
  through the embedding table, each encoder's connector output), one
  sequence per example with positions 0..L-1; pre-RMSNorm blocks of
  causal grouped-query attention with rotary positions and a SwiGLU
  MLP; final RMSNorm; logits over the (cut) vocabulary;
* the loss: cross-entropy of every position whose next position is a
  text token, against that token, summed over the global batch and
  divided by the number of such positions.

Example contents follow the data contract of the packed batches: the
example with batch id ``sid`` (1-based, instance-major order) has text
tokens ``default_rng((0, sid, crc32("tok"))).integers(1, vocab,
max(text, 1))`` and, per encoder, embeddings
``default_rng((0, sid, crc32(name))).standard_normal((n, embed_dim))``.
A text split into several parts takes its tokens in order, each part
``text // parts`` long and the last the rest.

``quant`` rounds every matmul operand: ``None`` is the reference;
``"fp8"`` (per-tensor scaled float8_e4m3fn) is the lower-precision
control.
"""
from __future__ import annotations

import functools
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import refmath as rm  # noqa: E402
from refmath import leaf_norms  # noqa: E402,F401

LLM_BUCKETS = (1024, 2048, 4096, 8192, 16384)
EMBED_STD = 0.02  # the published initializer range (Qwen2-7B)
ENC_BUCKETS = {"vision": (1536, 2304, 4608), "audio": (1536,)}


def param_shapes(model: dict) -> dict:
    """Nested dict of ``(shape, std)`` leaves, laid out as the packed
    batches' model stacks them (layers on a leading axis)."""
    D, F, L, V = model["d_model"], model["d_ff"], model["n_layers"], model["vocab_size"]
    H, Hkv = model["n_heads"], model["n_kv_heads"]
    hd = D // H
    mat = rm.matrix
    tree = {
        "embed": ((V, D), EMBED_STD),
        "layers": {
            "attn_norm": ((L, D), None), "mlp_norm": ((L, D), None),
            "wq": mat(L, D, H * hd), "wk": mat(L, D, Hkv * hd),
            "wv": mat(L, D, Hkv * hd), "wo": mat(L, H * hd, D),
            "w_gate": mat(L, D, F), "w_up": mat(L, D, F), "w_down": mat(L, F, D),
        },
        "final_norm": ((D,), None),
        "lm_head": mat(D, V),
    }
    for e in model["encoders"]:
        De, Fe, Le, ds = e["d_model"], e["d_ff"], e["n_layers"], e["downsample"]
        tree[f"encoder_{e['name']}"] = {
            "input_proj": mat(e["embed_dim"], De),
            "conn_in": mat(De * ds, D),
            "conn_out": mat(D, D),
            "layers": {
                "attn_norm": ((Le, De), None), "mlp_norm": ((Le, De), None),
                "wq": mat(Le, De, De), "wk": mat(Le, De, De),
                "wv": mat(Le, De, De), "wo": mat(Le, De, De),
                "w_in": mat(Le, De, Fe), "w_out": mat(Le, Fe, De),
            },
            "final_norm": ((De,), None),
        }
    return tree


def init_params(model: dict, seed: int, dtype=jnp.bfloat16):
    return rm.init_params(param_shapes(model), seed, dtype)


@functools.partial(jax.jit, static_argnames=("enc", "theta", "quant"))
def encoder_forward(p, embeds, n, *, enc, theta, quant):
    """embeds [Tb, embed_dim] (rows >= n are padding) -> connector rows
    [Tb // downsample, d_llm]; rows past ceil(n / downsample) are
    padding."""
    mm = rm.matmul(quant)
    name, heads, ds, depth = enc
    x = mm("te,ed->td", embeds, p["input_proj"])
    block = jax.checkpoint(functools.partial(
        rm.block, mm, heads=heads, kv_heads=heads, theta=theta, causal=False,
        norm=rm.layer_norm, mlp=rm.gelu_mlp))
    for i in range(depth):
        x = block(rm.layer(p["layers"], i), x, n)
    x = rm.rms_norm(x, p["final_norm"])
    x = jnp.where((jnp.arange(x.shape[0]) < n)[:, None], x, 0.0)
    x = x.reshape(x.shape[0] // ds, x.shape[1] * ds)
    return mm("te,ed->td", rm.gelu(mm("td,de->te", x, p["conn_in"])), p["conn_out"])


def _encoder_vjp(p, embeds, n, cot, *, enc, theta, quant):
    _, back = jax.vjp(lambda q: encoder_forward(q, embeds, n, enc=enc, theta=theta,
                                                quant=quant), p)
    return back(cot)[0]


encoder_grad = jax.jit(_encoder_vjp, static_argnames=("enc", "theta", "quant"))


def _llm_loss(p, enc_rows, tokens, is_text, labels, n, *, shape, quant):
    """Summed cross-entropy of one example and its count."""
    mm = rm.matmul(quant)
    heads, kv_heads, theta, depth = shape
    x = jnp.where(is_text[:, None], p["embed"][tokens].astype(jnp.float32), enc_rows)
    block = jax.checkpoint(functools.partial(
        rm.block, mm, heads=heads, kv_heads=kv_heads, theta=theta, causal=True,
        norm=rm.rms_norm, mlp=rm.swiglu))
    for i in range(depth):
        x = block(rm.layer(p["layers"], i), x, n)
    x = rm.rms_norm(x, p["final_norm"])
    T = x.shape[0]
    rows = rm.row_block(T)

    @jax.checkpoint
    def ce(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * rows, rows)
        lb = jax.lax.dynamic_slice_in_dim(labels, i * rows, rows)
        logits = mm("td,dv->tv", xb, p["lm_head"])
        gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[:, None], -1)[:, 0]
        return jnp.where(lb >= 0, jax.nn.logsumexp(logits, -1) - gold, 0.0).sum()

    return jax.lax.map(ce, jnp.arange(T // rows)).sum()


@functools.partial(jax.jit, static_argnames=("shape", "quant"))
def llm_grad(p, enc_rows, tokens, is_text, labels, n, *, shape, quant):
    """(summed loss, grads of the LLM leaves, grads of the encoder rows)."""
    f = functools.partial(_llm_loss, shape=shape, quant=quant)
    loss, (gp, ge) = jax.value_and_grad(f, argnums=(0, 1))(
        p, enc_rows, tokens, is_text, labels, n)
    return loss, gp, ge


# ----------------------------------------------------------------------
# Data movement and accumulation between the gradient programs, each one
# jitted call whatever an example's lengths (they are operands here).
# ----------------------------------------------------------------------
@jax.jit
def _place_rows(enc_rows, out, start, rows):
    """enc_rows with out[:rows] written at rows start .. start + rows - 1."""
    i = jnp.arange(enc_rows.shape[0]) - start
    hit = (i >= 0) & (i < rows)
    return jnp.where(hit[:, None], out[jnp.clip(i, 0, out.shape[0] - 1)], enc_rows)


@functools.partial(jax.jit, static_argnames=("length",))
def _take_rows(ge, start, rows, *, length):
    """[length, D]: ge's rows start .. start + rows - 1, then zeros."""
    i = jnp.arange(length)
    return jnp.where((i < rows)[:, None], ge[jnp.clip(start + i, 0, ge.shape[0] - 1)], 0.0)


@jax.jit
def _zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


@functools.partial(jax.jit, donate_argnums=0)
def _accumulate(acc, g):
    """acc + g, where g holds the top-level subtrees an example reached."""
    return {k: jax.tree_util.tree_map(jnp.add, v, g[k]) if k in g else v
            for k, v in acc.items()}


@functools.partial(jax.jit, donate_argnums=0)
def _divide(tree, count):
    return jax.tree_util.tree_map(lambda a: a / count, tree)


@jax.jit
def _square_sums(leaves):
    return [jnp.sum(jnp.square(g)) for g in leaves]


# ----------------------------------------------------------------------
# Example contents and layout.
# ----------------------------------------------------------------------
def example_rng(sid: int, tag: str) -> np.random.Generator:
    return np.random.default_rng((0, sid, zlib.crc32(tag.encode())))


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"length {n} exceeds the largest reference bucket {buckets[-1]}")


def layout(ex, sid: int, model: dict):
    """The example's LLM sequence: token ids, text mask, labels, and the
    start of each encoder's rows."""
    ds = {e["name"]: e["downsample"] for e in model["encoders"]}
    meta = {"vision": ex.vision, "audio": ex.audio}
    text_tokens = example_rng(sid, "tok").integers(
        1, model["vocab_size"], max(ex.text, 1), dtype=np.int32)
    parts = max(1, sum(1 for m in ex.order if m == "text"))
    tpart = ex.text // parts
    tokens, is_text, starts = [], [], {}
    ti = seen = 0
    for m in ex.order:
        if m == "text":
            n = ex.text - tpart * (parts - 1) if seen == parts - 1 else tpart
            tokens.append(text_tokens[ti:ti + n])
            is_text.append(np.ones(n, bool))
            ti += n
            seen += 1
        else:
            n = -(-meta[m] // ds[m])
            starts[m] = sum(len(t) for t in tokens)
            tokens.append(np.zeros(n, np.int32))
            is_text.append(np.zeros(n, bool))
    tokens = np.concatenate(tokens)
    is_text = np.concatenate(is_text)
    labels = np.full(len(tokens), -1, np.int32)
    nxt = is_text[1:]
    labels[:-1] = np.where(nxt, tokens[1:], -1)
    return tokens, is_text, labels, starts


# ----------------------------------------------------------------------
# One training step of the reference.
# ----------------------------------------------------------------------
def batch_grads(params, examples, model: dict, quant=None, *,
                llm_buckets=LLM_BUCKETS, enc_buckets=None):
    """params: float32 tree.  examples: the global batch, a list of
    example sizes in batch-id order (id = index + 1).  Returns (mean
    loss, grads of the mean loss, number of supervised positions)."""
    enc_buckets = enc_buckets or ENC_BUCKETS
    theta = float(model["rope_theta"])
    shape = (model["n_heads"], model["n_kv_heads"], theta, model["n_layers"])
    encs = {e["name"]: e for e in model["encoders"]}
    D = model["d_model"]
    llm_keys = ("embed", "layers", "final_norm", "lm_head")
    llm_p = {k: params[k] for k in llm_keys}
    acc = None
    loss_sum = 0.0
    count = 0
    for sid, ex in enumerate(examples, start=1):
        tokens, is_text, labels, starts = layout(ex, sid, model)
        L = len(tokens)
        Lb = _bucket(L, llm_buckets)
        enc_rows = jnp.zeros((Lb, D), jnp.float32)
        enc_in = {}
        for name, start in starts.items():
            e = encs[name]
            n = ex.vision if name == "vision" else ex.audio
            Tb = _bucket(n, enc_buckets[name])
            emb = np.zeros((Tb, e["embed_dim"]), np.float32)
            emb[:n] = example_rng(sid, name).standard_normal((n, e["embed_dim"]))
            key = (name, e["n_heads"], e["downsample"], e["n_layers"])
            out = encoder_forward(params[f"encoder_{name}"], emb, n, enc=key,
                                  theta=theta, quant=quant)
            rows = -(-n // e["downsample"])
            enc_rows = _place_rows(enc_rows, out, start, rows)
            enc_in[name] = (emb, n, key, start, rows, out.shape[0])
        pad = Lb - L
        ls, gp, ge = llm_grad(
            llm_p, enc_rows, np.pad(tokens, (0, pad)), np.pad(is_text, (0, pad)),
            np.pad(labels, (0, pad), constant_values=-1), L, shape=shape, quant=quant)
        g = dict(gp)
        for name, (emb, n, key, start, rows, tout) in enc_in.items():
            cot = _take_rows(ge, start, rows, length=tout)
            g[f"encoder_{name}"] = encoder_grad(
                params[f"encoder_{name}"], emb, n, cot, enc=key, theta=theta, quant=quant)
        if acc is None:
            acc = _zeros_like(params)
        acc = _accumulate(acc, g)
        loss_sum += float(ls)
        count += int((labels >= 0).sum())
    return loss_sum / count, _divide(acc, count), count


# ----------------------------------------------------------------------
# The optimizer the configuration states (AdamW, global-norm clipping,
# decay on leaves of rank >= 2), with its moments held on the host.
# ----------------------------------------------------------------------
@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("keep",))
def _adam_leaf(p, g, mu, nu, scale, t, lr, b1, b2, eps, wd, *, keep):
    g = g * scale
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    mhat = mu / (1 - b1 ** t)
    vhat = nu / (1 - b2 ** t)
    delta = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    return (p - lr * delta).astype(keep).astype(jnp.float32), mu, nu


def adam_step(params, grads, state, opt: dict, keep=jnp.bfloat16):
    """One AdamW step; ``state`` = {"t", "mu", "nu"} with host moments
    (None before the first step).  The new parameters are kept as the
    configuration stores them (``keep``, its dtype): an update under
    half a unit in the last place of a stored weight is lost there, as
    it is in any run that stores them so.  Returns (params, state, clip
    scale)."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    gl = tdef.flatten_up_to(grads)
    gnorm = np.sqrt(sum(float(s) for s in _square_sums(gl)))
    scale = min(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    t = state["t"] + 1
    mus = state["mu"] or [np.zeros(p.shape, np.float32) for p in leaves]
    nus = state["nu"] or [np.zeros(p.shape, np.float32) for p in leaves]
    out_p, out_mu, out_nu = [], [], []
    for p, g, mu, nu in zip(leaves, gl, mus, nus):
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        np_, mu_, nu_ = _adam_leaf(p, g, jnp.asarray(mu), jnp.asarray(nu), scale,
                                   float(t), opt["lr"], opt["b1"], opt["b2"],
                                   opt["eps"], wd, keep=jnp.dtype(keep))
        out_p.append(np_)
        out_mu.append(np.asarray(mu_))
        out_nu.append(np.asarray(nu_))
    return (jax.tree_util.tree_unflatten(tdef, out_p),
            {"t": t, "mu": out_mu, "nu": out_nu}, scale)
