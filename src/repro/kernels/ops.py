"""Jit'd public wrappers for the Pallas kernels.

``interpret=None`` follows the platform: on a TPU the kernels compile
through Mosaic, anywhere else they run in the Pallas interpreter (the
correctness-validation mode of the CPU tests).

XLA cannot partition a Mosaic kernel, so call sites inside a step that
runs on a DP mesh go through :func:`per_dp_shard`.
"""
from __future__ import annotations

from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.grouped_gemm import grouped_matmul as _gmm
from repro.kernels.selective_scan import selective_scan as _scan
from repro.sharding.specs import dp_axes_of, dp_shards_of

__all__ = ["flash_attention_op", "grouped_matmul_op", "selective_scan_op",
           "default_interpret", "per_dp_shard"]


def default_interpret() -> bool:
    """Interpret off the TPU, compile on it -- decided by the platform
    alone, so nothing can put a TPU run into the interpreter."""
    return jax.default_backend() != "tpu"


def per_dp_shard(fn, *args, replicated=()):
    """``fn(*args, *replicated)`` run per DP shard of the mesh set by
    ``jax.set_mesh``: a ``shard_map`` over its DP axes that splits every
    one of ``args`` on its leading (batch) dim, hands every shard the
    whole of each ``replicated`` array (weights), and joins the outputs
    on their leading dim.  Without a DP mesh, plain ``fn``."""
    mesh = jax.sharding.get_abstract_mesh()
    dp, n = dp_axes_of(mesh), dp_shards_of(mesh)
    if n == 1:
        return fn(*args, *replicated)
    for a in args:
        if a.shape[0] % n:
            raise ValueError(f"batch dim {a.shape[0]} of a {a.shape} kernel "
                             f"operand does not divide over {n} DP shards")
    specs = (P(dp),) * len(args) + (P(),) * len(replicated)
    return jax.shard_map(fn, in_specs=specs, out_specs=P(dp),
                         check_vma=False)(*args, *replicated)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_kv",
                                   "interpret", "block_skip"))
def flash_attention_op(q, k, v, q_seg, kv_seg, q_pos, kv_pos, *,
                       causal=True, window=None, block_q=128, block_kv=128,
                       interpret=None, block_skip=True):
    interpret = default_interpret() if interpret is None else interpret
    return _flash(q, k, v, q_seg, kv_seg, q_pos, kv_pos, causal=causal,
                  window=window, block_q=block_q, block_kv=block_kv,
                  interpret=interpret, block_skip=block_skip)


@partial(jax.jit, static_argnames=("block_d", "chunk", "interpret",
                                   "return_state"))
def selective_scan_op(u, delta, A, B, C, D, seg, *, block_d=128, chunk=64,
                      interpret=None, return_state=False):
    interpret = default_interpret() if interpret is None else interpret
    return _scan(u, delta, A, B, C, D, seg, block_d=block_d, chunk=chunk,
                 interpret=interpret, return_state=return_state)


@partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def grouped_matmul_op(x, w, group_offsets, *, block_m=128, block_n=128,
                      interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return _gmm(x, w, group_offsets, block_m=block_m, block_n=block_n,
                interpret=interpret)
