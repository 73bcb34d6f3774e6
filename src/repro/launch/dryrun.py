import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input shape) pair on the
production meshes -- single-pod (16,16) and multi-pod (2,16,16) -- with
ShapeDtypeStruct inputs (no allocation), records memory_analysis(),
cost_analysis() and the HLO collective schedule, and emits the roofline
terms (deliverable g).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

NOTE: the XLA_FLAGS line above MUST run before any other import (JAX
locks the device count on first init); do not set it globally.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import INPUT_SHAPES, get_config, input_specs, step_kind
from repro.configs.registry import ARCHITECTURES
from repro.launch.mesh import dp_axes_of, dp_shards_of, make_production_mesh
from repro.launch.roofline import HW, analyze, get_hw
from repro.sharding.specs import (
    batch_specs,
    cache_sharding_specs,
    opt_state_specs,
    param_specs,
    to_shardings,
)


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode counts
    one token per request."""
    n = cfg.active_param_count()
    if shape.kind == "decode":
        tokens = shape.global_batch  # one new token per request
    else:
        tokens = shape.seq_len * shape.global_batch
        if shape.kind == "train":
            return 6.0 * n * tokens  # fwd + bwd
        return 2.0 * n * tokens
    return 2.0 * n * tokens


def build_step(cfg, shape, mesh, comm_mode="a2a"):
    """Returns (fn, example_args, in_shardings, donate) for the pair."""
    from repro.models.model import init_params
    from repro.serving.serve_step import make_serve_step
    from repro.training.optimizer import adamw_init
    from repro.training.train_step import make_prefill_step, make_train_step

    dp_axes = dp_axes_of(mesh)
    dp = dp_shards_of(mesh)
    specs = input_specs(cfg, shape.name, dp_shards=dp)
    kind = step_kind(cfg, shape)

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))
    )
    p_specs = param_specs(cfg, params_shape, mesh)

    if kind == "train":
        opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
        o_specs = opt_state_specs(p_specs)
        fn = make_train_step(cfg, mesh=mesh, dp_axes=dp_axes, comm_mode=comm_mode)
        args = (params_shape, opt_shape, specs)
        in_sh = (p_specs, o_specs, batch_specs(specs, dp_axes))
        donate = (0, 1)
    elif kind == "prefill":
        fn = make_prefill_step(cfg, mesh=mesh, dp_axes=dp_axes, comm_mode=comm_mode)
        args = (params_shape, specs)
        in_sh = (p_specs, batch_specs(specs, dp_axes))
        donate = ()
    else:  # decode
        fn = make_serve_step(cfg)
        cache = specs["cache"]
        c_specs = cache_sharding_specs(cfg, cache, dp_axes, mesh)
        B = specs["tokens"].shape[0]
        tok_spec = (
            jax.sharding.PartitionSpec(dp_axes) if B % dp == 0 and B >= dp
            else jax.sharding.PartitionSpec()
        )
        args = (params_shape, specs["tokens"], cache, specs["t"])
        in_sh = (p_specs, tok_spec, c_specs, jax.sharding.PartitionSpec())
        donate = (2,)
    return fn, args, in_sh, donate


def _compile_once(cfg, shape, mesh, comm_mode):
    fn, args, in_sh, donate = build_step(cfg, shape, mesh, comm_mode)
    with mesh:
        jitted = jax.jit(fn, in_shardings=to_shardings(in_sh, mesh),
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    return mem, cost, hlo


def _stacks(cfg, kind):
    """(tag, trip_count, probe_unroll) for each layer scan in the step.
    Used for the roofline extrapolation: XLA cost_analysis prices a
    while-loop body once, so we probe with the body holding 1 and k
    layers and extrapolate linearly to the real trip count."""
    if cfg.family == "hybrid":
        trip = (cfg.shared_attn_every if kind == "decode"
                else cfg.n_layers // cfg.shared_attn_every)
    else:
        trip = cfg.n_layers
    k2 = 3 if trip % 2 else 2
    out = [("llm", trip, k2)]
    if kind != "decode" and cfg.family != "audio":
        for e in cfg.encoders:
            if e.n_layers > 0:
                out.append((e.name, e.n_layers, 3 if e.n_layers % 2 else 2))
    return out


def _probe_cfg(cfg, tag, k):
    import dataclasses as dc

    enc = tuple(
        dc.replace(e, scan_unroll=k if e.name == tag else 1) for e in cfg.encoders
    )
    return dc.replace(
        cfg,
        attention_impl="chunked_unrolled",
        scan_unroll=k if tag == "llm" else 1,
        encoders=enc,
    )


def _extract(cost, hlo):
    from repro.launch.roofline import collective_bytes

    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
    }


def run_pair(arch: str, shape_name: str, *, multi_pod: bool, comm_mode="a2a",
             roofline: bool = True, hw: HW | None = None,
             cfg_override=None, tag_suffix: str = "") -> dict:
    cfg = cfg_override or get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    kind = step_kind(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if kind is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": "sub-quadratic attention required"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    # The production mesh is a v5e pod slice (launch/mesh.py).
    hw = hw or get_hw("TPU v5 lite",
                      chips=int(np.prod(list(mesh.shape.values()))))
    t0 = time.time()
    try:
        # Pass 1: production form (scan-over-layers) -- compile success,
        # memory_analysis, baseline HLO.
        mem, cost0, hlo0 = _compile_once(cfg, shape, mesh, comm_mode)
        t_main = time.time() - t0

        flops = bytes_ = None
        coll = None
        if roofline:
            # Pass 2..n: roofline probes with unrolled inner scans;
            # per-stack unroll 1 vs k extrapolates loop trip counts.
            _, c1, h1 = _compile_once(_probe_cfg(cfg, "llm", 1), shape, mesh, comm_mode)
            base = _extract(c1, h1)
            flops, bytes_ = base["flops"], base["bytes"]
            coll = dict(base["coll"])
            for tag, trip, k2 in _stacks(cfg, kind):
                _, c2, h2 = _compile_once(_probe_cfg(cfg, tag, k2), shape, mesh, comm_mode)
                probe = _extract(c2, h2)
                scale = (trip - 1) / (k2 - 1)
                flops += (probe["flops"] - base["flops"]) * scale
                bytes_ += (probe["bytes"] - base["bytes"]) * scale
                for key in coll:
                    coll[key] += (probe["coll"][key] - base["coll"][key]) * scale
    except Exception as e:  # noqa: BLE001 -- report, don't crash the sweep
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "FAILED", "error": f"{type(e).__name__}: {e}",
            "trace": traceback.format_exc()[-2000:],
        }
    mem_d = {
        "argument_size": getattr(mem, "argument_size_in_bytes", None),
        "output_size": getattr(mem, "output_size_in_bytes", None),
        "temp_size": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_size": getattr(mem, "generated_code_size_in_bytes", None),
    }
    if not roofline:
        flops, bytes_ = float(cost0.get("flops", 0)), float(cost0.get("bytes accessed", 0))
        from repro.launch.roofline import collective_bytes

        coll = collective_bytes(hlo0)
    rep = analyze(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        cost={"flops": flops, "bytes accessed": bytes_},
        hlo_text="", memory=mem_d,
        model_flops_global=_model_flops(cfg, shape), hw=hw,
    )
    rep.coll_breakdown = {k: int(v) for k, v in coll.items()}
    rep.coll_bytes_per_chip = float(coll["total"])
    rep.collective_s = rep.coll_bytes_per_chip / hw.ici_bw
    terms = {"compute": rep.compute_s, "memory": rep.memory_s,
             "collective": rep.collective_s}
    rep.dominant = max(terms, key=terms.get)
    row = rep.row()
    row.update({
        "status": "ok", "kind": kind, "comm_mode": comm_mode,
        "roofline_corrected": roofline,
        "compile_s": round(time.time() - t0, 1), "main_compile_s": round(t_main, 1),
    })
    print(f"[{arch} x {shape_name} @ {mesh_name}] memory_analysis: {mem_d}")
    print(f"[{arch} x {shape_name} @ {mesh_name}] cost_analysis(corrected): "
          f"flops={flops:.3e} bytes={bytes_:.3e} coll={coll['total']:.3e}")
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--comm-mode", default="a2a",
                    choices=["a2a", "ragged", "allgather", "gather"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--assigned-only", action="store_true",
                    help="only the 10 assigned archs (skip paper MLLMs)")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else [
        a for a in ARCHITECTURES if not args.assigned_only or not a.startswith("mllm")
    ]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                f = out / f"{tag}__{args.comm_mode}.json"
                if f.exists():
                    results.append(json.loads(f.read_text()))
                    print(f"cached {tag}")
                    continue
                print(f"=== {tag} (comm={args.comm_mode}) ===", flush=True)
                # Roofline probes on the single-pod mesh only (the table
                # is single-pod; multi-pod proves the pod axis shards).
                row = run_pair(arch, shape, multi_pod=mp,
                               comm_mode=args.comm_mode, roofline=not mp)
                f.write_text(json.dumps(row, indent=1, default=str))
                results.append(row)
                status = row["status"]
                extra = row.get("error", "")[:200] if status == "FAILED" else (
                    f"dominant={row.get('dominant')} compile={row.get('compile_s')}s"
                )
                print(f"--> {status} {extra}", flush=True)

    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    bad = [r for r in results if r["status"] == "FAILED"]
    print(f"\nSummary: {ok} ok, {sk} skipped, {len(bad)} failed of {len(results)}")
    for r in bad:
        print(f"  FAILED {r['arch']} x {r['shape']} @ {r['mesh']}: {r['error'][:200]}")


if __name__ == "__main__":
    main()
