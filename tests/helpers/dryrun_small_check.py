"""Miniature dry-run: the full launch machinery (param/batch/cache
shardings, jit lower+compile, roofline extraction) on an 8-device host
mesh with smoke configs.  Validates what the production 512-device
dry-run does, cheaply, inside pytest."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.roofline import collective_bytes
from repro.sharding.specs import (
    batch_specs,
    cache_sharding_specs,
    opt_state_specs,
    param_specs,
    to_shardings,
)


def tiny_specs(cfg, kind, dp):
    """input_specs at reduced sizes for smoke configs."""
    import jax.numpy as jnp

    i32, bf16 = jnp.int32, jnp.bfloat16
    S, cap = dp, 256
    if kind == "train":
        if cfg.encoders and cfg.family != "audio":
            specs = {
                "tokens": jax.ShapeDtypeStruct((S, cap // 2), i32),
                "text_dst": jax.ShapeDtypeStruct((S, cap // 2), i32),
                "llm_seg": jax.ShapeDtypeStruct((S, cap), i32),
                "llm_pos": jax.ShapeDtypeStruct((S, cap), i32),
                "llm_labels": jax.ShapeDtypeStruct((S, cap), i32),
            }
            for e in cfg.encoders:
                ce = 128 * e.downsample
                co = ce // e.downsample
                chunk = max(co // S, 8)
                specs.update({
                    f"enc_{e.name}_embeds": jax.ShapeDtypeStruct((S, ce, e.embed_dim), bf16),
                    f"enc_{e.name}_seg": jax.ShapeDtypeStruct((S, ce), i32),
                    f"enc_{e.name}_pos": jax.ShapeDtypeStruct((S, ce), i32),
                    f"enc_{e.name}_dst": jax.ShapeDtypeStruct((S, co), i32),
                    f"enc_{e.name}_plan_pre_gather_dense": jax.ShapeDtypeStruct((S, S * chunk), i32),
                    f"enc_{e.name}_plan_post_gather_dense": jax.ShapeDtypeStruct((S, co), i32),
                    f"enc_{e.name}_plan_post_mask": jax.ShapeDtypeStruct((S, co), jax.numpy.bool_),
                    f"enc_{e.name}_plan_global_gather": jax.ShapeDtypeStruct((S, co), i32),
                })
            return specs
        if cfg.family == "audio":
            e = cfg.encoders[0]
            ce = 128
            return {
                "tokens": jax.ShapeDtypeStruct((S, cap), i32),
                "labels": jax.ShapeDtypeStruct((S, cap), i32),
                "seg": jax.ShapeDtypeStruct((S, cap), i32),
                "pos": jax.ShapeDtypeStruct((S, cap), i32),
                f"enc_{e.name}_embeds": jax.ShapeDtypeStruct((S, ce, e.embed_dim), bf16),
                f"enc_{e.name}_seg": jax.ShapeDtypeStruct((S, ce), i32),
                f"enc_{e.name}_pos": jax.ShapeDtypeStruct((S, ce), i32),
                f"enc_{e.name}_seg_out": jax.ShapeDtypeStruct((S, ce), i32),
                f"enc_{e.name}_pos_out": jax.ShapeDtypeStruct((S, ce), i32),
                f"enc_{e.name}_plan_pre_gather_dense": jax.ShapeDtypeStruct((S, S * max(ce // S, 8)), i32),
                f"enc_{e.name}_plan_post_gather_dense": jax.ShapeDtypeStruct((S, ce), i32),
                f"enc_{e.name}_plan_post_mask": jax.ShapeDtypeStruct((S, ce), jax.numpy.bool_),
                f"enc_{e.name}_plan_global_gather": jax.ShapeDtypeStruct((S, ce), i32),
            }
        return {
            "tokens": jax.ShapeDtypeStruct((S, cap), i32),
            "labels": jax.ShapeDtypeStruct((S, cap), i32),
            "seg": jax.ShapeDtypeStruct((S, cap), i32),
            "pos": jax.ShapeDtypeStruct((S, cap), i32),
        }
    # decode
    from repro.configs.registry import cache_specs

    return {
        "tokens": jax.ShapeDtypeStruct((8, 1), i32),
        "t": jax.ShapeDtypeStruct((), i32),
        "cache": cache_specs(cfg, 8, 64),
    }


def run(arch, kind, multi_pod):
    from repro.models.model import init_params
    from repro.serving.serve_step import make_serve_step
    from repro.training.optimizer import adamw_init
    from repro.training.train_step import make_train_step

    cfg = get_config(arch).smoke()
    if multi_pod:
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        dp_axes = ("pod", "data")
    else:
        mesh = make_mesh((4, 2), ("data", "model"))
        dp_axes = ("data",)
    dp = 4
    specs = tiny_specs(cfg, kind, dp)
    params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    p_specs = param_specs(cfg, params_shape, mesh)

    with mesh:
        if kind == "train":
            opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
            fn = make_train_step(cfg, mesh=mesh, dp_axes=dp_axes)
            in_sh = (p_specs, opt_state_specs(p_specs), batch_specs(specs, dp_axes))
            args = (params_shape, opt_shape, specs)
        else:
            fn = make_serve_step(cfg)
            c_specs = cache_sharding_specs(cfg, specs["cache"], dp_axes, mesh)
            in_sh = (p_specs, jax.sharding.PartitionSpec(dp_axes), c_specs,
                     jax.sharding.PartitionSpec())
            args = (params_shape, specs["tokens"], specs["cache"], specs["t"])
        lowered = jax.jit(fn, in_shardings=to_shardings(in_sh, mesh)).lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
    assert cost.get("flops", 0) > 0
    assert mem.temp_size_in_bytes >= 0
    print(f"ok {arch} {kind} multi_pod={multi_pod} flops={cost['flops']:.2e} "
          f"coll={coll['total']:.2e}")
    return True


def main():
    assert len(jax.devices()) == 8
    ok = True
    for arch, kinds in (
        ("qwen3_8b", ("train", "decode")),
        ("grok_1_314b", ("train",)),
        ("falcon_mamba_7b", ("train", "decode")),
        ("zamba2_2_7b", ("decode",)),
        ("llava_next_mistral_7b", ("train",)),
        ("whisper_large_v3", ("train", "decode")),
    ):
        for kind in kinds:
            for mp in (False, True):
                ok &= run(arch, kind, mp)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
