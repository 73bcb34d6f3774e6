"""Train / prefill step factories.

``make_train_step(cfg, mesh, ...)`` returns a jit-able function
``(params, opt_state, batch) -> (params, opt_state, metrics)`` whose
forward pass embeds the orchestrator's communicator exchange (the
composed all-to-all) between encoder phases and the LLM backbone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, with_attention_backend
from repro.core.communicator import apply_comm_plan
from repro.models.model import forward
from repro.obs.spans import phase
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update

__all__ = [
    "METRIC_HELP",
    "check_opt_state",
    "make_exchange",
    "make_loss_fn",
    "make_train_step",
    "make_prefill_step",
]

# Catalog of every key the train-step metrics dict can carry.  The
# observability plane (repro.obs.ledger) republishes these host scalars
# as ``train_metric{name=...}`` gauges; this mapping is the single place
# their meaning is documented.
METRIC_HELP = {
    "loss": "mean next-token cross-entropy over supervised positions",
    "aux_loss": "MoE load-balance auxiliary loss (0 for dense families)",
    "tokens": "supervised positions in the step's global batch",
    "moe_dropped_frac": "routed tokens dropped at expert capacity "
                        "(0 on the drop-free grouped backend)",
    "moe_max_expert_load": "largest per-expert load fraction "
                           "(1/n_experts = perfectly balanced routing)",
    "grad_norm": "global gradient L2 norm",
}

# The optimizer-state contract ``make_train_step`` / ``adamw_update``
# expect -- and what a checkpoint must therefore carry.  Kept next to
# the step factory so the contract and its consumer move together.
OPT_STATE_KEYS = ("mu", "nu", "step")


def check_opt_state(params, opt_state) -> None:
    """Validate a (restored) optimizer state against the train-step
    contract: ``{"mu", "nu", "step"}`` with both moment trees congruent
    with ``params`` (same treedef, same leaf shapes) and a scalar step.

    Raises ``ValueError`` with the first violation -- this is what
    ``repro.checkpoint.state`` runs on every restore, so a checkpoint
    from an incompatible architecture fails loudly instead of crashing
    deep inside the jitted update."""
    if not isinstance(opt_state, dict) or set(opt_state) != set(OPT_STATE_KEYS):
        got = sorted(opt_state) if isinstance(opt_state, dict) else type(opt_state)
        raise ValueError(f"opt_state must have keys {OPT_STATE_KEYS}, got {got}")
    p_leaves, p_def = jax.tree_util.tree_flatten(params)
    for moment in ("mu", "nu"):
        m_leaves, m_def = jax.tree_util.tree_flatten(opt_state[moment])
        if m_def != p_def:
            raise ValueError(
                f"opt_state[{moment!r}] tree structure does not match params")
        for pl, ml in zip(p_leaves, m_leaves):
            if tuple(pl.shape) != tuple(ml.shape):
                raise ValueError(
                    f"opt_state[{moment!r}] leaf shape {tuple(ml.shape)} != "
                    f"params leaf shape {tuple(pl.shape)}")
    step = jnp.asarray(opt_state["step"])
    if step.ndim != 0:
        raise ValueError(f"opt_state['step'] must be a scalar, got {step.shape}")


def make_exchange(cfg: ModelConfig, mesh, dp_axes, *, mode: str = "a2a"):
    """Build the orchestrator's device-side exchange closure.

    Reads the per-encoder plan arrays out of the batch; moves encoder
    output tokens [S, cap_out_shard, D] -> destination shards.  With
    ``mesh=None`` (single-host tests) the exchange degrades to the
    'gather' mode: a plain global take with identical semantics."""

    def exchange_factory(batch):
        def exchange(name: str, enc_tok: jnp.ndarray) -> jnp.ndarray:
            S, T, D = enc_tok.shape
            plan = {
                "pre_gather_dense": batch[f"enc_{name}_plan_pre_gather_dense"],
                "post_gather_dense": batch[f"enc_{name}_plan_post_gather_dense"],
                "post_mask": batch[f"enc_{name}_plan_post_mask"],
                "global_gather": batch[f"enc_{name}_plan_global_gather"],
            }
            cap_out = plan["post_mask"].shape[-1]
            flat = enc_tok.reshape(S * T, D)
            if mesh is None:
                idx = plan["global_gather"].reshape(-1)
                mask = plan["post_mask"].reshape(-1)
                out = jnp.where(mask[:, None], jnp.take(flat, idx, axis=0), 0)
            else:
                out = apply_comm_plan(flat, plan, mesh, dp_axes, mode=mode)
            return out.reshape(S, cap_out, D)

        return exchange

    return exchange_factory


def make_loss_fn(cfg: ModelConfig, mesh=None, dp_axes=("data",), *,
                 comm_mode="a2a", attention_backend: str | None = None):
    """``attention_backend`` overrides ``cfg.attention_impl`` for every
    attention site inside the jitted loss/grad (e.g. "flash" to train on
    the Pallas path, "reference" for an oracle run)."""
    cfg = with_attention_backend(cfg, attention_backend)
    exchange_factory = make_exchange(cfg, mesh, dp_axes, mode=comm_mode)

    def loss_fn(params, batch):
        ex = exchange_factory(batch) if cfg.encoders else None
        loss_sum, n, aux = forward(cfg, params, batch, exchange=ex)
        n = jnp.maximum(n, 1)
        # moe family returns an aux metrics dict; only the load-balance
        # loss enters the objective, the rest surface as metrics.
        aux_loss = aux["lb_loss"] if isinstance(aux, dict) else aux
        loss = loss_sum / n + 0.01 * aux_loss
        metrics = {"loss": loss_sum / n, "aux_loss": aux_loss, "tokens": n}
        if isinstance(aux, dict):
            metrics["moe_dropped_frac"] = aux["dropped_frac"]
            metrics["moe_max_expert_load"] = aux["expert_load"].max()
        return loss, metrics

    return loss_fn


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig | None = None,
    mesh=None,
    dp_axes=("data",),
    *,
    comm_mode: str = "a2a",
    attention_backend: str | None = None,
):
    opt_cfg = opt_cfg or AdamWConfig()
    loss_fn = make_loss_fn(cfg, mesh, dp_axes, comm_mode=comm_mode,
                           attention_backend=attention_backend)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        with phase("optimizer"):
            params, opt_state, opt_metrics = adamw_update(params, grads, opt_state,
                                                          opt_cfg)
        return params, opt_state, {**metrics, **opt_metrics}

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh=None, dp_axes=("data",), *,
                      comm_mode: str = "a2a",
                      attention_backend: str | None = None):
    """Forward-only (inference prefill): returns per-stream loss metrics.
    Serving prefill reuses the same packed-stream forward; logits for
    sampling come from the serve path."""
    loss_fn = make_loss_fn(cfg, mesh, dp_axes, comm_mode=comm_mode,
                           attention_backend=attention_backend)

    def prefill_step(params, batch):
        _, metrics = loss_fn(params, batch)
        return metrics

    return prefill_step


def init_train_state(cfg: ModelConfig, key):
    from repro.models.model import init_params

    params = init_params(cfg, key)
    return params, adamw_init(params)
