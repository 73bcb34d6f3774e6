"""Chip bring-up smoke test: the main paths, once each, on a TPU.

    python chip_smoke.py             # one chip: kernels, training, serving
    python chip_smoke.py --chips 4   # four chips: DP training vs one device

One chip, three phases in one process:

1. kernels -- flash attention (forward and backward), the grouped GEMM
   (forward and backward) and the selective scan (forward and backward),
   compiled by Mosaic at mllm_10b / MoE / Mamba widths, each compared
   with ``repro.kernels.ref``;
2. training -- ``mllm_10b`` at its published widths, cut only in depth
   and vocabulary, through ``repro.launch.train.train`` (orchestrator ->
   PrefetchingLoader -> train step -> jit) with 4 post-balanced DP
   instances on the one device: a warm-up step, then 5 timed steps;
3. serving -- ``serving.engine.Engine`` on the whole ``olmo_1b`` with a
   paged KV pool of about 2 GB, 8 requests of 64-512 prompt tokens and
   32 greedy new tokens each.

``--chips 4`` runs only the DP comparison: the same cut ``mllm_10b``
under ``--mesh host`` (one DP instance per chip, all-to-all exchange)
against the single-device loop on the same parameters and batches, two
steps each.

Weights are random from a fixed seed.  Times printed are bring-up
readings, not benchmark numbers.  The script fails (non-zero exit, no
result line) when JAX finds no TPU, when it is run outside a checkout
of the repository, and when any phase fails.  Its last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# mllm_10b cut to one chip's share: depth and vocabulary only.
DEPTH = {"llm": 2, "vision": 2, "audio": 2}
VOCAB_DIVISOR = 8
# The padded audio encoder stream holds ceil(3 * examples / d) + 1 rows
# of 1504 slots per instance.  On the configured "chunked" attention
# backend its dense [T, T] tiles do not fit the chip even at 2 examples
# per instance (18 GB); the flash kernel skips the dead tiles and the
# step needs about 14.5 GB at 2 examples per instance (TPU compiler's
# memory analysis for one v5e).
ATTENTION = "flash"
DP_INSTANCES = 4
EXAMPLES_PER_INSTANCE = 2
TIMED_STEPS = 5


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------
# Phase 1: kernels at real widths against repro.kernels.ref.
# ----------------------------------------------------------------------
# Tolerances, as max |kernel - ref| / max |ref| per output:
#  * flash and grouped GEMM take and return bf16 (8-bit mantissa, unit
#    roundoff 2^-9 ~ 2e-3) and accumulate in f32 in a different order
#    than the oracle: 2e-2 leaves a few bf16 ulps of headroom;
#  * the scan runs in f32 on both sides, same recurrence order; only
#    exp/reduction rounding differs over 4096 steps: 1e-3.
# The oracles run at "highest" matmul precision, so they are f32-exact
# references and not bf16-pass matmuls.
FLASH_TOL = GMM_TOL = 2e-2
SCAN_TOL = 1e-3


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _report(name: str, errs: dict[str, float], tol: float) -> None:
    worst = max(errs.values())
    print(f"kernel {name}: " + " ".join(f"{k}={v:.2e}" for k, v in errs.items())
          + f" (tol {tol:.0e})", flush=True)
    check(worst <= tol, f"{name} differs from repro.kernels.ref: {errs}")


def kernel_phase(*, T=4096, H=28, Hkv=4, D=128, block=512, M=8192, K=4096,
                 N=1536, E=8, di=8192, state=16) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.ops import (flash_attention_op, grouped_matmul_op,
                                   selective_scan_op)

    rng = np.random.default_rng(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    highest = jax.default_matmul_precision("highest")

    # Flash attention: mllm_10b's LLM attention (GQA 28/4, head dim 128)
    # over one packed stream of four examples.
    lens = [T // 4, 3 * T // 8, T // 4, T // 8]
    seg = jnp.asarray(np.repeat(np.arange(1, 5), lens)[None], jnp.int32)
    pos = jnp.asarray(np.concatenate([np.arange(n) for n in lens])[None],
                      jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, H, T, D)), bf16)
    k = jnp.asarray(rng.normal(size=(1, Hkv, T, D)), bf16)
    v = jnp.asarray(rng.normal(size=(1, Hkv, T, D)), bf16)
    do = jnp.asarray(rng.normal(size=(1, H, T, D)), bf16)

    def flash(q, k, v):
        return flash_attention_op(q, k, v, seg, seg, pos, pos, causal=True,
                                  block_q=block, block_kv=block)

    out, vjp = jax.vjp(flash, q, k, v)
    dq, dk, dv = vjp(do)
    g = H // Hkv

    @jax.jit
    def flash_ref_group(qg, kg, vg, dog):
        # One KV group at a time keeps the oracle's [g, T, T] score and
        # probability residuals within a few GB.
        def f(qg, kg, vg):
            rep = lambda x: jnp.repeat(x, g, axis=1)  # noqa: E731
            return ref.flash_attention_ref(qg, rep(kg), rep(vg), seg, seg,
                                           pos, pos, causal=True)
        o, back = jax.vjp(f, qg, kg, vg)
        return (o,) + back(dog)

    with highest:
        parts = [flash_ref_group(q[:, j * g:(j + 1) * g], k[:, j:j + 1],
                                 v[:, j:j + 1], do[:, j * g:(j + 1) * g])
                 for j in range(Hkv)]
    want = [jnp.concatenate([p[i] for p in parts], axis=1) for i in range(4)]
    _report("flash_attention", {
        "out": _rel_err(out, want[0]), "dq": _rel_err(dq, want[1]),
        "dk": _rel_err(dk, want[2]), "dv": _rel_err(dv, want[3])}, FLASH_TOL)
    del q, k, v, do, out, dq, dk, dv, parts, want

    # Grouped GEMM: MoE expert FFN widths, uneven groups, padded tail.
    sizes = rng.multinomial(M - M // 8, rng.dirichlet(np.ones(E)))
    offs = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]), jnp.int32)
    x = jnp.asarray(rng.normal(size=(M, K)), bf16)
    w = jnp.asarray(rng.normal(size=(E, K, N)) / np.sqrt(K), bf16)
    dy = jnp.asarray(rng.normal(size=(M, N)), bf16)
    out, vjp = jax.vjp(lambda x, w: grouped_matmul_op(x, w, offs), x, w)
    dx, dw = vjp(dy)
    with highest:
        want, back = jax.vjp(
            jax.jit(lambda x, w: ref.grouped_matmul_ref(x, w, offs)), x, w)
        dx_ref, dw_ref = back(dy)
    _report("grouped_matmul", {
        "out": _rel_err(out, want), "dx": _rel_err(dx, dx_ref),
        "dw": _rel_err(dw, dw_ref)}, GMM_TOL)
    del x, w, dy, out, dx, dw, want, dx_ref, dw_ref

    # Selective scan: Mamba-1 widths (d_inner 8192, state 16), two
    # packed segments and a padded tail.
    u = jnp.asarray(rng.normal(size=(T, di)), f32)
    delta = jnp.asarray(np.abs(rng.normal(0.05, 0.02, size=(T, di))), f32)
    A = jnp.asarray(-np.abs(rng.normal(1.0, 0.3, size=(di, state))), f32)
    B = jnp.asarray(rng.normal(size=(T, state)), f32)
    C = jnp.asarray(rng.normal(size=(T, state)), f32)
    Dp = jnp.asarray(rng.normal(size=(di,)), f32)
    sseg = np.ones(T, np.int32)
    sseg[T // 2:] = 2
    sseg[-64:] = 0
    sseg = jnp.asarray(sseg)
    dy = jnp.asarray(rng.normal(size=(T, di)), f32)
    args = (u, delta, A, B, C, Dp)
    y, vjp = jax.vjp(lambda *a: selective_scan_op(*a, sseg), *args)
    grads = vjp(dy)
    with highest:
        want, back = jax.vjp(
            jax.jit(lambda *a: ref.selective_scan_ref(*a, sseg)), *args)
        grads_ref = back(dy)
    _report("selective_scan", {"y": _rel_err(y, want), **{
        f"d{n}": _rel_err(a, b)
        for n, a, b in zip(("u", "delta", "A", "B", "C", "D"), grads,
                           grads_ref)}}, SCAN_TOL)


# ----------------------------------------------------------------------
# Phase 2: training through the launcher.
# ----------------------------------------------------------------------
def cut_mllm_10b():
    """mllm_10b with every width as published; depth and vocabulary cut
    to fit one v5e chip (16 GB) with AdamW state, on the flash attention
    backend (see ATTENTION)."""
    from repro.configs import get_config

    base = get_config("mllm_10b")
    encoders = tuple(dataclasses.replace(e, n_layers=DEPTH[e.name])
                     for e in base.encoders)
    cfg = dataclasses.replace(base, n_layers=DEPTH["llm"],
                              vocab_size=base.vocab_size // VOCAB_DIVISOR,
                              encoders=encoders, attention_impl=ATTENTION)
    print(f"cut: llm layers {base.n_layers} -> {cfg.n_layers}")
    for e0, e1 in zip(base.encoders, cfg.encoders):
        print(f"cut: {e0.name} layers {e0.n_layers} -> {e1.n_layers}")
    print(f"cut: vocabulary {base.vocab_size} -> {cfg.vocab_size} "
          f"(first 1/{VOCAB_DIVISOR} of the rows)")
    print(f"backend: attention {base.attention_impl} -> {cfg.attention_impl}; "
          f"{DP_INSTANCES} DP instances x {EXAMPLES_PER_INSTANCE} examples")
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} params={cfg.param_count() / 1e9:.2f}B",
          flush=True)
    return cfg


def _train_args(mesh: str, steps: int):
    from repro.launch.train import parse_args

    return parse_args(["--arch", "mllm_10b", "--d", str(DP_INSTANCES),
                       "--per", str(EXAMPLES_PER_INSTANCE),
                       "--steps", str(steps), "--mesh", mesh])


def train_phase(cfg) -> None:
    import math

    from repro.launch.train import train
    from repro.utils import CompileWatch

    with CompileWatch() as watch:
        records = train(cfg, _train_args("none", 1 + TIMED_STEPS))
    for r in records:
        tag = "warm-up" if r is records[0] else "timed"
        print(f"train step {r['step']} ({tag}): loss={r['loss']:.4f} "
              f"grad_norm={r['grad_norm']:.4f} step_ms={r['step_ms']:.1f} "
              f"compiles={r['compiles']}", flush=True)
    print(f"train compile: {watch.compile_s:.1f} s backend compile, "
          f"{watch.cache_hits} persistent-cache hits, warm-up step "
          f"{records[0]['step_ms'] / 1e3:.1f} s", flush=True)
    check(len(records) == 1 + TIMED_STEPS, f"{len(records)} steps ran")
    check(all(math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"])
              for r in records), "non-finite loss or grad norm")
    recompiles = sum(r["compiles"] for r in records[1:])
    check(recompiles == 0, f"{recompiles} compilations after warm-up")


DP_STEPS = 2
# Relative limits on the DP loop's loss and grad norm against the
# single-device loop, per step.  Both run the same bf16 model on the
# same data; partitioning moves where f32 partial sums are reduced.
# Step 1 starts from the same parameters: measured 2.6e-6 (loss) and
# 1.45e-4 (grad norm) on TPU v5 lite x4.  Step 2 follows an AdamW
# update, whose first step moves every weight by +-lr after the sign of
# its gradient, so reduction-order noise flips the tiny gradients:
# measured 5.3e-4 and 9.6e-4.  A fault that loses one shard's work
# shows far above both: with one shard's attention output zeroed, the
# smoke-width mllm_10b on a 4-device CPU mesh moved 2.3e-2 in the first
# step's grad norm, and 8.7e-3 (loss) and 1.1e-1 (grad norm) in the
# second step.
DP_TOL = (1e-3, 5e-3)


def dp_phase(cfg) -> None:
    """--mesh host (one DP instance per device, a2a exchange) against the
    single-device loop on the same parameters and batches: DP_STEPS
    steps each, nothing compiled after the first, each step's loss and
    grad norm within its DP_TOL."""
    import jax

    from repro.launch.train import train

    n = len(jax.devices())
    check(n == DP_INSTANCES, f"--chips 4 needs 4 devices, found {n}")
    dp = train(cfg, _train_args("host", DP_STEPS))
    one = train(cfg, _train_args("none", DP_STEPS))
    check(len(dp) == len(one) == DP_STEPS, f"{len(dp)}, {len(one)} steps ran")
    for a, b, tol in zip(dp, one, DP_TOL):
        rel = {k: abs(a[k] - b[k]) / abs(b[k]) for k in ("loss", "grad_norm")}
        print(f"step {a['step']}: dp x{n} (mesh host, a2a) loss={a['loss']:.6f} "
              f"grad_norm={a['grad_norm']:.6f} step_ms={a['step_ms']:.1f} "
              f"compiles={a['compiles']} | one device loss={b['loss']:.6f} "
              f"grad_norm={b['grad_norm']:.6f} step_ms={b['step_ms']:.1f} "
              f"compiles={b['compiles']} | relative difference loss "
              f"{rel['loss']:.2e} grad_norm {rel['grad_norm']:.2e} "
              f"(tol {tol:.0e})", flush=True)
        check(max(rel.values()) <= tol,
              f"DP step {a['step']} disagrees with the single-device step: "
              f"{rel}")
    recompiles = sum(r["compiles"] for r in dp[1:] + one[1:])
    check(recompiles == 0, f"{recompiles} compilations after warm-up")


# ----------------------------------------------------------------------
# Phase 3: serving.
# ----------------------------------------------------------------------
NEW_TOKENS = 32


def serve_phase(cfg=None, *, prompt_lens=(64, 128, 192, 256, 320, 384, 448, 512),
                num_blocks=1025) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import EngineConfig, get_config
    from repro.models.model import init_params
    from repro.serving.engine import Engine
    from repro.serving.engine.request import Request
    from repro.serving.serve_step import greedy_sample
    from repro.utils import round_up

    cfg = cfg or get_config("olmo_1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    block = 16
    max_len = round_up(max(prompt_lens) + NEW_TOKENS, block)
    ecfg = EngineConfig(
        block_size=block, num_blocks=num_blocks, max_num_seqs=len(prompt_lens),
        token_budget=sum(prompt_lens) + len(prompt_lens),
        max_model_len=max_len, prefill_pad=128, decode_pad=len(prompt_lens),
        # One prefill group: every prompt is admitted in the first step.
        prefill_waste=float(len(prompt_lens)))
    pool_gb = 2 * cfg.n_layers * num_blocks * block * cfg.n_kv_heads \
        * cfg.head_dim_ * 2 / 1e9
    print(f"serve: {cfg.name} params={cfg.param_count() / 1e9:.2f}B, KV pool "
          f"{num_blocks} x {block} slots ({pool_gb:.2f} GB)", flush=True)

    def checked_greedy(logits, key=None):
        # Greedy, except that a row with any non-finite logit yields -1,
        # which the check below rejects.
        ok = jnp.isfinite(logits).all(axis=-1, keepdims=True)
        return jnp.where(ok, greedy_sample(logits), -1)

    rng = np.random.default_rng(0)
    requests = [Request(i, rng.integers(1, cfg.vocab_size, size=n),
                        max_new_tokens=NEW_TOKENS)
                for i, n in enumerate(prompt_lens)]
    engine = Engine(cfg, ecfg, params, sample_fn=checked_greedy)
    t0 = time.perf_counter()
    report = engine.run(requests)
    wall = time.perf_counter() - t0
    print(report.summary())
    for r in engine.requests:
        check(r.state.name == "FINISHED", f"request {r.req_id} {r.state}")
        check(len(r.output_tokens) == NEW_TOKENS,
              f"request {r.req_id}: {len(r.output_tokens)} tokens")
        check(min(r.output_tokens) >= 0,
              f"request {r.req_id}: non-finite logits")
    print(f"serve: {report.n_finished}/{len(requests)} finished, "
          f"{report.generated_tokens} tokens in {wall:.1f} s (compiles "
          f"included); TTFT mean {report.ttft_s_mean * 1e3:.1f} ms, ITL "
          f"{report.decode_ms_mean:.2f} ms per decode step", flush=True)


# ----------------------------------------------------------------------
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the DP comparison across four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this check runs on the chip only", file=sys.stderr)
        return 1
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of the repository "
              f"(src/repro missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils import setup_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {setup_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    cfg = cut_mllm_10b()
    if args.chips == 4:
        dp_phase(cfg)
    else:
        kernel_phase()
        print(f"phase kernels done at {time.perf_counter() - t0:.0f} s", flush=True)
        train_phase(cfg)
        print(f"phase train done at {time.perf_counter() - t0:.0f} s", flush=True)
        serve_phase()
        print(f"phase serve done at {time.perf_counter() - t0:.0f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
