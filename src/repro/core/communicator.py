"""Node-wise All-to-All Communicator -- device side (paper S5.2.1).

The dispatcher decides a rearrangement Pi on the host; this module moves
the actual token payloads between DP shards.  Three modes, matching the
paper's comparison (Fig. 5 / Fig. 12):

  * ``a2a``       the paper's All-to-All Batch Communicator:
                  ``shard_map`` + :func:`jax.lax.ragged_all_to_all`.
                  Per-shard traffic is O(max_i L_i), independent of d
                  (paper Eq. 4).
  * ``allgather`` the strawman: every shard gathers every mini-batch and
                  slices out its own -- O((d-1) max_i L_i) traffic
                  (paper Eq. 3).  Kept as a selectable mode so the HLO
                  collective-byte comparison in EXPERIMENTS.md reproduces
                  Fig. 12 structurally.
  * ``gather``    XLA-native: a global `jnp.take` under pjit; XLA SPMD
                  chooses the collectives.  Used as a third point in the
                  perf iteration.

Everything here works on PACKED token buffers: a global array
``[d, capacity, ...]`` sharded on its first (DP) axis; each shard holds
its examples' tokens contiguously in slot order.  Padded phases flatten
valid tokens before transport and re-pad at the destination -- i.e. the
communicator never moves padding (a TPU-friendly bonus of token-level
transport).

Portability note: ``jax.lax.ragged_all_to_all`` does not execute on
XLA:CPU (ThunkEmitter unimplemented), so the default ``a2a`` mode is a
dense ``jax.lax.all_to_all`` over per-peer chunks padded to a static
chunk capacity (host-computed max over peers).  That still lowers to a
genuine ``all-to-all`` HLO op with volume O(d * chunk_cap) per shard --
the balancing makes chunk_cap small, preserving the paper's Eq. 4
behavior -- and it runs on CPU, TPU and GPU alike.  ``mode="ragged"``
keeps the exact ragged collective for real TPU runs (traced/lowered in
tests, executed only on hardware that supports it).
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.rearrangement import Rearrangement
from repro.data.packing import CapacityOverflow
from repro.utils import round_up as _round_up

__all__ = ["CommPlan", "build_comm_plan", "apply_comm_plan", "plan_to_device"]


@dataclasses.dataclass
class CommPlan:
    """Host-built static-shape plan for one payload exchange.

    All integer arrays are int32.  Shapes:
      pre_gather   [d, cap_in]   send-buffer build: dest-major token order
      input_offsets, send_sizes, output_offsets, recv_sizes  [d, d]
      post_gather  [d, cap_out]  recv-buffer -> final packed layout
      post_mask    [d, cap_out]  True on valid (non-pad) token positions
    """

    d: int
    cap_in: int
    cap_out: int
    pre_gather: np.ndarray
    input_offsets: np.ndarray
    send_sizes: np.ndarray
    output_offsets: np.ndarray
    recv_sizes: np.ndarray
    post_gather: np.ndarray
    post_mask: np.ndarray
    # Global-gather fallback: final token p of shard i comes from global
    # flat index global_gather[i, p] of the [d*cap_in] source array.
    global_gather: np.ndarray
    # Dense all_to_all emulation (CPU/TPU-portable): static per-peer chunk.
    chunk_cap: int
    pre_gather_dense: np.ndarray  # [d, d*chunk_cap]
    post_gather_dense: np.ndarray  # [d, cap_out]
    # Host-only metadata: destination packed-layout offsets per example
    # (flat, aligned with the source Rearrangement's entries).
    dst_starts: np.ndarray | None = None

    def comm_bytes(self, bytes_per_token: int) -> dict[str, int]:
        """Analytic traffic accounting (paper Eq. 3 vs 4)."""
        off_diag = self.send_sizes.copy()
        np.fill_diagonal(off_diag, 0)
        ragged = int(off_diag.sum()) * bytes_per_token
        dense = int(self.d * (self.d - 1) * self.chunk_cap) * bytes_per_token
        ag = int(self.d * (self.d - 1) * self.cap_in) * bytes_per_token
        return {"ragged": ragged, "a2a_dense": dense, "allgather": ag}


def _layout(insts: np.ndarray, slots: np.ndarray, lengths: np.ndarray, d: int):
    """Token start offset of each example in its shard's packed buffer,
    ordering examples by slot; returns (starts[n], totals[d])."""
    starts = np.zeros(len(insts), dtype=np.int64)
    totals = np.zeros(d, dtype=np.int64)
    for i in range(d):
        sel = np.where(insts == i)[0]
        sel = sel[np.argsort(slots[sel])]
        off = 0
        for k in sel:
            starts[k] = off
            off += lengths[k]
        totals[i] = off
    return starts, totals


def build_comm_plan(
    pi: Rearrangement, cap_in: int, cap_out: int, *, chunk_pad_to: int = 8,
    src_starts: np.ndarray | None = None, chunk_cap: int | None = None,
    stream: str = "exchange",
) -> CommPlan:
    """Compile a Rearrangement into static-shape transport arrays.

    ``src_starts``: explicit token offset of each example in its SOURCE
    shard buffer (flat, aligned with pi's entries).  Defaults to packed
    contiguous layout in src_slot order; the orchestrator passes explicit
    starts when the source layout has alignment gaps (downsample) or
    padded rows (audio).  A plan that does not fit a capacity raises
    :class:`CapacityOverflow` naming ``stream``.
    """
    d = pi.d
    n = pi.n
    lengths = pi.lengths.astype(np.int64)
    if src_starts is None:
        src_starts, src_totals = _layout(pi.src_inst, pi.src_slot, lengths, d)
        if src_totals.max(initial=0) > cap_in:
            raise CapacityOverflow(
                stream, f"cap_in={cap_in} < max shard tokens {src_totals.max()}")
    else:
        src_starts = np.asarray(src_starts, dtype=np.int64)
        if n and (src_starts + lengths).max() > cap_in:
            raise CapacityOverflow(
                stream, f"cap_in={cap_in} < max src end {(src_starts + lengths).max()}")
    dst_starts, dst_totals = _layout(pi.dst_inst, pi.dst_slot, lengths, d)
    if dst_totals.max(initial=0) > cap_out:
        raise CapacityOverflow(
            stream, f"cap_out={cap_out} < max shard tokens {dst_totals.max()}")

    pre_gather = np.zeros((d, cap_in), dtype=np.int32)
    input_offsets = np.zeros((d, d), dtype=np.int32)
    send_sizes = np.zeros((d, d), dtype=np.int32)
    output_offsets = np.zeros((d, d), dtype=np.int32)
    recv_sizes = np.zeros((d, d), dtype=np.int32)
    post_gather = np.zeros((d, cap_out), dtype=np.int32)
    post_mask = np.zeros((d, cap_out), dtype=bool)
    global_gather = np.zeros((d, cap_out), dtype=np.int32)

    # Send side: per source shard, order examples dest-major then dst_slot.
    send_pos_of_example = np.zeros(n, dtype=np.int64)  # position in send buffer
    for s in range(d):
        ex = np.where(pi.src_inst == s)[0]
        ex = ex[np.lexsort((pi.dst_slot[ex], pi.dst_inst[ex]))]
        off = 0
        for t in range(d):
            input_offsets[s, t] = off
            for k in ex[pi.dst_inst[ex] == t]:
                send_pos_of_example[k] = off
                l = int(lengths[k])
                pre_gather[s, off : off + l] = np.arange(
                    src_starts[k], src_starts[k] + l, dtype=np.int32
                )
                off += l
            send_sizes[s, t] = off - input_offsets[s, t]

    # Recv side: source-major chunks.
    for t in range(d):
        off = 0
        for s in range(d):
            output_offsets[s, t] = off
            recv_sizes[t, s] = send_sizes[s, t]
            off += send_sizes[s, t]

    # Dense-emulation layout: per-peer chunks padded to a static capacity.
    # ``chunk_cap`` may be supplied by the caller (FIXED across steps so
    # the jitted step never recompiles); overflow raises and the data
    # pipeline resamples.
    max_send = int(send_sizes.max(initial=0))
    if chunk_cap is None:
        chunk_cap = _round_up(max(max_send, 1), chunk_pad_to)
    elif max_send > chunk_cap:
        raise CapacityOverflow(
            stream, f"peer chunk {max_send} > static chunk_cap {chunk_cap}")
    pre_gather_dense = np.zeros((d, d * chunk_cap), dtype=np.int32)
    for s in range(d):
        for t in range(d):
            sz = int(send_sizes[s, t])
            src = pre_gather[s, input_offsets[s, t] : input_offsets[s, t] + sz]
            pre_gather_dense[s, t * chunk_cap : t * chunk_cap + sz] = src

    # Post gather: final packed layout per destination shard.
    post_gather_dense = np.zeros((d, cap_out), dtype=np.int32)
    for t in range(d):
        ex = np.where(pi.dst_inst == t)[0]
        ex = ex[np.argsort(pi.dst_slot[ex])]
        for k in ex:
            s = int(pi.src_inst[k])
            # position of k's tokens inside s->t chunk:
            within = send_pos_of_example[k] - input_offsets[s, t]
            recv_start = output_offsets[s, t] + within
            l = int(lengths[k])
            dst = int(dst_starts[k])
            post_gather[t, dst : dst + l] = np.arange(
                recv_start, recv_start + l, dtype=np.int32
            )
            post_gather_dense[t, dst : dst + l] = s * chunk_cap + int(within) + np.arange(
                l, dtype=np.int32
            )
            post_mask[t, dst : dst + l] = True
            global_gather[t, dst : dst + l] = s * cap_in + np.arange(
                src_starts[k], src_starts[k] + l, dtype=np.int32
            )

    return CommPlan(
        d=d,
        cap_in=cap_in,
        cap_out=cap_out,
        pre_gather=pre_gather,
        input_offsets=input_offsets,
        send_sizes=send_sizes,
        output_offsets=output_offsets,
        recv_sizes=recv_sizes,
        post_gather=post_gather,
        post_mask=post_mask,
        global_gather=global_gather,
        chunk_cap=chunk_cap,
        pre_gather_dense=pre_gather_dense,
        post_gather_dense=post_gather_dense,
        dst_starts=dst_starts,
    )


_PLAN_KEYS = (
    "pre_gather", "input_offsets", "send_sizes", "output_offsets",
    "recv_sizes", "post_gather", "post_mask", "global_gather",
    "pre_gather_dense", "post_gather_dense",
)


def plan_to_device(plan: CommPlan) -> dict[str, jnp.ndarray]:
    """The arrays the jitted step consumes (shard these on the DP axis)."""
    return {k: jnp.asarray(getattr(plan, k)) for k in _PLAN_KEYS}


def plan_shardings(dp_axes: tuple[str, ...]) -> dict[str, P]:
    """PartitionSpecs for :func:`plan_to_device` outputs."""
    return {k: P(dp_axes) for k in _PLAN_KEYS}


# ----------------------------------------------------------------------
# Device-side exchange.
# ----------------------------------------------------------------------
COMM_MODES = ("a2a", "ragged", "allgather", "gather")


def apply_comm_plan(
    x: jnp.ndarray,
    plan_arrays: dict[str, jnp.ndarray],
    mesh: Mesh,
    dp_axes: tuple[str, ...],
    *,
    mode: str = "a2a",
) -> jnp.ndarray:
    """Rearrange packed token payloads across DP shards.

    Args:
      x: global [total_shards * cap_in, ...] array (first dim sharded over
        ``dp_axes``); *token* leading dim.
      plan_arrays: from :func:`plan_to_device`; first dims sharded likewise.
      mode: "a2a" (dense all_to_all emulation, portable), "ragged"
        (paper-exact ragged_all_to_all, TPU), "allgather" (strawman,
        paper Eq. 3), "gather" (XLA-native global take).

    Returns [total_shards * cap_out, ...] global array, same sharding.
    """
    d = int(np.prod([mesh.shape[a] for a in dp_axes]))
    # post_mask is the one plan array every mode carries.
    cap_out = plan_arrays["post_mask"].shape[-1]
    feat = x.shape[1:]
    axis = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    row = P(dp_axes)

    def masked(res, mask):
        return jnp.where(mask.reshape(mask.shape + (1,) * (res.ndim - 1)), res, 0)

    if mode == "gather":
        # Global take; XLA SPMD inserts the collectives it prefers.
        idx = plan_arrays["global_gather"].reshape(-1)
        mask = plan_arrays["post_mask"].reshape(-1)
        res = jnp.take(x, idx, axis=0)
        return jnp.where(mask.reshape((-1,) + (1,) * len(feat)), res, 0)

    if mode == "allgather":
        def body(xs, gg, mask):
            allx = jax.lax.all_gather(xs, axis_name=axis, tiled=True)
            return masked(jnp.take(allx, gg[0], axis=0), mask[0])

        return jax.shard_map(
            body, mesh=mesh, in_specs=(row, row, row), out_specs=row
        )(x, plan_arrays["global_gather"], plan_arrays["post_mask"])

    if mode == "a2a":
        chunk_cap = plan_arrays["pre_gather_dense"].shape[-1] // d

        def body(xs, pgd, post, mask):
            send = jnp.take(xs, pgd[0], axis=0)  # [d*chunk, ...]
            send = send.reshape((d, chunk_cap) + feat)
            recv = jax.lax.all_to_all(
                send, axis_name=axis, split_axis=0, concat_axis=0
            )  # [d, chunk, ...]: entry s = chunk from source shard s
            recv = recv.reshape((d * chunk_cap,) + feat)
            return masked(jnp.take(recv, post[0], axis=0), mask[0])

        return jax.shard_map(
            body, mesh=mesh, in_specs=(row, row, row, row), out_specs=row
        )(x, plan_arrays["pre_gather_dense"], plan_arrays["post_gather_dense"],
          plan_arrays["post_mask"])

    if mode == "ragged":
        def body(xs, pg, io, ss, oo, rs, post, mask):
            send = jnp.take(xs, pg[0], axis=0)
            out = jnp.zeros((cap_out,) + feat, xs.dtype)
            out = jax.lax.ragged_all_to_all(
                send, out,
                io[0].astype(jnp.int32), ss[0].astype(jnp.int32),
                oo[0].astype(jnp.int32), rs[0].astype(jnp.int32),
                axis_name=axis,
            )
            return masked(jnp.take(out, post[0], axis=0), mask[0])

        return jax.shard_map(
            body, mesh=mesh, in_specs=(row,) + (row,) * 7, out_specs=row
        )(
            x,
            plan_arrays["pre_gather"],
            plan_arrays["input_offsets"],
            plan_arrays["send_sizes"],
            plan_arrays["output_offsets"],
            plan_arrays["recv_sizes"],
            plan_arrays["post_gather"],
            plan_arrays["post_mask"],
        )

    raise ValueError(f"unknown communicator mode {mode!r}")
