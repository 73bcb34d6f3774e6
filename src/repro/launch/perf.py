import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""S-Perf hillclimb driver (EXPERIMENTS.md).

Re-lowers a chosen (arch x shape) pair with one optimization knob
changed and reports the delta on every roofline term vs the cached
baseline.  Experiments are named; each run writes
experiments/perf/<pair>__<variant>.json.

    PYTHONPATH=src python -m repro.launch.perf --exp qwen3_windowed
    PYTHONPATH=src python -m repro.launch.perf --list
"""
import argparse
import dataclasses
import json
from pathlib import Path

from repro.configs import INPUT_SHAPES, get_config
from repro.core.cost_model import llm_cost_model
from repro.launch.dryrun import run_pair
from repro.telemetry import nnls_fit


def _variant(cfg, **kw):
    enc_kw = kw.pop("encoders_map", None)
    if enc_kw:
        kw["encoders"] = tuple(dataclasses.replace(e, **enc_kw) for e in cfg.encoders)
    return dataclasses.replace(cfg, **kw)


# Each experiment: (arch, shape, {variant_name: cfg_kwargs_or_run_kwargs}).
EXPERIMENTS = {
    # 1. memory-dominant dense train: window-chunked segment attention
    #    (exploits post-balancing's bounded segment length).
    "qwen3_windowed": ("qwen3_8b", "train_4k", {
        "segwin4096": dict(cfg=dict(segment_window=4096)),
        "segwin4096_bq256": dict(cfg=dict(segment_window=4096, block_q=256,
                                          block_kv=256)),
    }),
    "h2o_windowed": ("h2o_danube_3_4b", "train_4k", {
        "segwin4096": dict(cfg=dict(segment_window=4096)),
    }),
    # 2. collective-bound MoE train: buffer sharding + capacity factor.
    "grok_collective": ("grok_1_314b", "train_4k", {
        "moe_shard_buf": dict(cfg=dict(moe_shard_buffers=True)),
        "cap1.0": dict(cfg=dict(capacity_factor=1.0)),
        "moe_shard_buf_cap1.0": dict(cfg=dict(moe_shard_buffers=True,
                                              capacity_factor=1.0)),
        "segwin4096": dict(cfg=dict(segment_window=4096)),
        "combined": dict(cfg=dict(moe_shard_buffers=True, capacity_factor=1.0,
                                  segment_window=4096)),
    }),
    # 3. the paper's own technique, end to end: communicator mode on the
    #    representative multimodal arch (Fig. 12 analog in compiled HLO).
    "mllm_comm": ("mllm_10b", "train_4k", {
        "allgather": dict(run=dict(comm_mode="allgather")),
        "gather": dict(run=dict(comm_mode="gather")),
        "segwin4096": dict(cfg=dict(segment_window=4096)),
    }),
    # 4. big-model representative: windowed attention at 84B.
    "mllm84_windowed": ("mllm_84b", "train_4k", {
        "segwin4096": dict(cfg=dict(segment_window=4096)),
    }),
}


def coeff_delta(arch, baseline_dir, *, mesh="16x16", comm="a2a"):
    """Calibrated-vs-analytic cost coefficients from cached dry-runs.

    Fits (alpha, beta) of the paper's f(S) to the XLA-priced FLOPs of
    every cached shape for this arch (features: linear = tokens,
    quadratic = batch * seq^2; train rows are normalized by 3x for the
    backward pass) via the telemetry NNLS, and compares the fitted
    quadratic/linear ratio ``lam`` against ``llm_cost_model``'s analytic
    one.  A large ratio means the hand-derived coefficients mis-model
    this architecture and the balancing objective is skewed -- exactly
    what ``AdaptiveCostModel`` corrects online.  Needs >= 2 cached
    shapes to be identifiable (returns None otherwise)."""
    import numpy as np

    X, y, used = [], [], []
    for f in sorted(Path(baseline_dir).glob(f"{arch}__*__{mesh}__{comm}.json")):
        row = json.loads(f.read_text())
        if row.get("status") != "ok" or row.get("kind") not in ("train", "prefill"):
            continue
        shape = INPUT_SHAPES.get(row.get("shape"))
        flops = row.get("flops_per_chip")
        if shape is None or not flops:
            continue
        tokens = float(shape.seq_len) * shape.global_batch
        X.append([tokens, shape.global_batch * float(shape.seq_len) ** 2])
        y.append(float(flops) / (3.0 if row["kind"] == "train" else 1.0))
        used.append(shape.name)
    if len(set(used)) < 2:
        return None
    c = nnls_fit(np.asarray(X), np.asarray(y))
    if c[0] <= 0:
        return None
    lam_cal = float(c[1] / c[0])
    lam_ana = llm_cost_model(get_config(arch)).lam
    return {
        "coeff_lam_analytic": lam_ana,
        "coeff_lam_calibrated": lam_cal,
        "coeff_lam_ratio": (lam_cal / lam_ana) if lam_ana else None,
        "coeff_fit_shapes": used,
    }


def show(row, base=None):
    if row["status"] != "ok":
        print(f"  !! {row['status']}: {row.get('error', row.get('reason'))}")
        return
    terms = {k: row[k] for k in ("compute_s", "memory_s", "collective_s")}
    line = "  " + "  ".join(f"{k[:-2]}={v:8.3f}s" for k, v in terms.items())
    line += f"  dominant={row['dominant']}  useful={row['useful_ratio']:.3f}"
    # Cached rows predate the ledger-projected MFU; recompute on the fly
    # so old experiment files display it too (same canonical formula).
    mfu = row.get("mfu_projected")
    if mfu is None:
        from repro.obs.ledger import projected_mfu
        mfu = projected_mfu(row["useful_ratio"], *terms.values())
    line += f"  mfu_proj={mfu:.3f}"
    if row.get("coeff_lam_ratio") is not None:
        line += (f"  lam(cal/ana)={row['coeff_lam_ratio']:.2f}x"
                 f" [{row['coeff_lam_calibrated']:.2e} vs"
                 f" {row['coeff_lam_analytic']:.2e}]")
    if base and base["status"] == "ok":
        deltas = []
        for k in terms:
            b = base[k]
            if b:
                deltas.append(f"{k[:-2]}:{row[k] / b:5.2f}x")
        line += "   [vs base " + " ".join(deltas) + "]"
    print(line, flush=True)


def main():
    from repro.launch.roofline import HW_PRESETS, get_hw

    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default=None)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="experiments/perf")
    ap.add_argument("--baseline-dir", default="experiments/dryrun")
    ap.add_argument("--hw", default="TPU v5 lite", choices=sorted(HW_PRESETS),
                    help="device kind whose peaks price the roofline terms")
    args = ap.parse_args()
    if args.list:
        for k, (a, s, vs) in EXPERIMENTS.items():
            print(f"{k}: {a} x {s} -> {sorted(vs)}")
        return

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    todo = [args.exp] if args.exp else list(EXPERIMENTS)
    for name in todo:
        arch, shape, variants = EXPERIMENTS[name]
        print(f"=== {name}: {arch} x {shape} ===", flush=True)
        base_f = Path(args.baseline_dir) / f"{arch}__{shape}__16x16__a2a.json"
        if base_f.exists():
            base = json.loads(base_f.read_text())
        else:
            print("  (computing baseline)", flush=True)
            base = run_pair(arch, shape, multi_pod=False,
                            hw=get_hw(args.hw, chips=256))
            base_f.write_text(json.dumps(base, indent=1, default=str))
        # Calibrated-vs-analytic f(S) coefficients for this arch (from
        # every cached dry-run shape); a ratio far from 1x flags an
        # architecture whose balancing objective is mis-modeled.
        # Applied to cached AND fresh rows (the fit improves as more
        # dry-run shapes land), and persisted back to the files.
        coeffs = coeff_delta(arch, args.baseline_dir)
        if coeffs and coeffs != {k: base.get(k) for k in coeffs}:
            base.update(coeffs)
            base_f.write_text(json.dumps(base, indent=1, default=str))
        print("  baseline:")
        show(base)
        for vname, spec in variants.items():
            f = out / f"{arch}__{shape}__{vname}.json"
            if f.exists():
                row = json.loads(f.read_text())
            else:
                cfg = get_config(arch)
                if "cfg" in spec:
                    cfg = _variant(cfg, **spec["cfg"])
                run_kw = spec.get("run", {})
                row = run_pair(arch, shape, multi_pod=False, cfg_override=cfg,
                               hw=get_hw(args.hw, chips=256), **run_kw)
                row["variant"] = vname
            if coeffs and coeffs != {k: row.get(k) for k in coeffs}:
                row.update(coeffs)
                f.write_text(json.dumps(row, indent=1, default=str))
            elif not f.exists():
                f.write_text(json.dumps(row, indent=1, default=str))
            print(f"  {vname}:")
            show(row, base)


if __name__ == "__main__":
    main()
