"""Packed-stream assembly for post-balanced batches.

A *stream* is one DP shard's token buffer [cap]: examples laid out
contiguously in destination-slot order, ``seg`` carrying a per-example
id (0 = padding), ``pos`` restarting at 0 per example.  Padded phases
(audio, paper S8) lay each example out in a fixed ``max_len`` row inside
the stream so the compute cost matches the padded cost model while the
same segment machinery applies.
"""
from __future__ import annotations

import numpy as np

__all__ = ["CapacityOverflow", "pack_stream", "pack_padded_stream", "random_tokens"]


class CapacityOverflow(ValueError):
    """A batch does not fit one of its static capacities.  ``stream``
    names the capacity: ``llm``, ``text``, an encoder's input stream by
    the encoder's name, or ``<encoder>.exchange`` for the static shapes
    of its exchange plan.  The data pipeline resamples on it."""

    def __init__(self, stream: str, message: str) -> None:
        super().__init__(f"{stream}: {message}")
        self.stream = stream


def pack_stream(
    dest_lengths: list[np.ndarray],
    cap: int,
    *,
    seg_ids: list[np.ndarray] | None = None,
    align: int = 1,
    stream: str = "unnamed",
) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
    """Build (seg [S,cap], pos [S,cap], starts per shard) for packed layout.

    ``seg_ids[i][j]``: id (>0) of example j on shard i; defaults to a
    running counter unique per shard.  ``align``: round each example's
    start offset up to this multiple (connector downsample alignment).
    ``stream`` names the stream in a :class:`CapacityOverflow`.
    """
    S = len(dest_lengths)
    seg = np.zeros((S, cap), np.int32)
    pos = np.zeros((S, cap), np.int32)
    starts: list[np.ndarray] = []
    for i, lens in enumerate(dest_lengths):
        off = 0
        st = np.zeros(len(lens), np.int64)
        for j, l in enumerate(np.asarray(lens, np.int64)):
            sid = int(seg_ids[i][j]) if seg_ids is not None else j + 1
            if off + l > cap:
                raise CapacityOverflow(stream,
                                       f"shard {i}: {off + l} tokens > cap {cap}")
            seg[i, off : off + l] = sid
            pos[i, off : off + l] = np.arange(l)
            st[j] = off
            off += int(l)
            off = -(-off // align) * align
        starts.append(st)
    return seg, pos, starts


def pack_padded_stream(
    dest_lengths: list[np.ndarray],
    cap: int,
    row_len: int,
    *,
    seg_ids: list[np.ndarray] | None = None,
    stream: str = "unnamed",
) -> tuple[np.ndarray, np.ndarray, list[np.ndarray]]:
    """Padded layout: example j of a shard occupies row j*row_len; tokens
    beyond its length stay seg=0 (padding).  cap must be >= rows*row_len.
    ``stream`` names the stream in a :class:`CapacityOverflow`."""
    S = len(dest_lengths)
    seg = np.zeros((S, cap), np.int32)
    pos = np.zeros((S, cap), np.int32)
    starts: list[np.ndarray] = []
    for i, lens in enumerate(dest_lengths):
        st = np.zeros(len(lens), np.int64)
        for j, l in enumerate(np.asarray(lens, np.int64)):
            off = j * row_len
            if off + row_len > cap:
                raise CapacityOverflow(stream,
                                       f"shard {i}: padded rows exceed cap {cap}")
            if l > row_len:
                raise CapacityOverflow(stream, f"example len {l} > row_len {row_len}")
            sid = int(seg_ids[i][j]) if seg_ids is not None else j + 1
            seg[i, off : off + l] = sid
            pos[i, off : off + l] = np.arange(l)
            st[j] = off
        starts.append(st)
    return seg, pos, starts


def random_tokens(rng: np.random.Generator, shape, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, size=shape, dtype=np.int32)
