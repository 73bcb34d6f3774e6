"""Dependency-free sharded pytree checkpoint store.

A checkpoint is a *directory* of per-leaf ``.npy`` shards plus one
``manifest.json`` that carries everything needed to rebuild the pytree
on a host that knows nothing about the writer:

  * the tree structure (nested dict/list/tuple skeleton with leaf
    placeholders), so restore needs no live template,
  * per-leaf dtype/shape and a SHA-256 content hash (corruption is
    *detected*, never silently restored),
  * optional mesh / ``PartitionSpec`` metadata per leaf -- the writer
    records how the array was sharded so :mod:`repro.checkpoint.elastic`
    can re-shard it host-side onto a different mesh,
  * a free-form JSON ``extras`` blob (data cursor, calibrator state,
    step counter -- anything :mod:`repro.checkpoint.state` bundles).

Atomic commit protocol: everything is written into ``<name>.tmp``, every
file (and the directory entry) is fsynced, and only then is the
directory renamed to its final name.  A crash mid-save therefore leaves
either the previous complete checkpoint untouched plus a ``.tmp`` litter
directory (ignored and garbage-collected by the manager), or nothing --
never a half-written checkpoint under a committed name.

:class:`CheckpointManager` adds the step-numbered directory layout
(``step_000042/``), a keep-last-K retention policy, and restore-with-
fallback: a corrupt newest checkpoint is flagged (renamed to
``*.corrupt``) and the next older complete one is restored instead.
"""
from __future__ import annotations

import dataclasses
import hashlib
import io
import json
import os
import re
import shutil
import time
from typing import Any, Iterator

import numpy as np

from repro.obs.spans import span

__all__ = [
    "CheckpointCorruptError",
    "CheckpointManager",
    "CheckpointOp",
    "LeafInfo",
    "load_manifest",
    "load_pytree",
    "save_pytree",
    "spec_to_meta",
]

MANIFEST = "manifest.json"
FORMAT_VERSION = 1
_STEP_RE = re.compile(r"^step_(\d+)$")


class CheckpointCorruptError(RuntimeError):
    """A committed checkpoint failed structural or hash verification."""


# ---------------------------------------------------------------------------
# Tree <-> (skeleton, leaves)


def _is_container(node: Any) -> bool:
    # PartitionSpec subclasses tuple; a specs tree must treat it as a
    # leaf, not recurse into its axis entries.
    if type(node).__name__ == "PartitionSpec":
        return False
    return isinstance(node, (dict, list, tuple))


def _flatten(tree: Any, path: str = "") -> Iterator[tuple[str, Any]]:
    """Depth-first (path, leaf) pairs; paths are '/'-joined keys."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flatten(tree[k], f"{path}/{k}" if path else str(k))
    elif _is_container(tree):
        for i, v in enumerate(tree):
            yield from _flatten(v, f"{path}/{i}" if path else str(i))
    else:
        yield path, tree


def _skeleton(tree: Any) -> Any:
    """JSON-able structure mirror with leaf markers."""
    if isinstance(tree, dict):
        items = {k: _skeleton(v) for k, v in tree.items()}
        return {"__kind__": "dict", "items": items}
    if _is_container(tree):
        kind = "list" if isinstance(tree, list) else "tuple"
        return {"__kind__": kind, "items": [_skeleton(v) for v in tree]}
    return {"__kind__": "leaf"}


def _unskeleton(skel: Any, path: str, leaves: dict[str, Any]) -> Any:
    kind = skel["__kind__"]
    if kind == "dict":
        return {
            k: _unskeleton(v, f"{path}/{k}" if path else str(k), leaves)
            for k, v in skel["items"].items()
        }
    if kind in ("list", "tuple"):
        seq = [
            _unskeleton(v, f"{path}/{i}" if path else str(i), leaves)
            for i, v in enumerate(skel["items"])
        ]
        return seq if kind == "list" else tuple(seq)
    return leaves[path]


def spec_to_meta(spec: Any) -> list[Any] | None:
    """A ``PartitionSpec`` (or tuple of axis names) as a JSON-able list.

    Entries are axis-name strings, lists of axis names, or ``None``.  A
    ``None`` spec maps to ``None`` (replicated / unsharded).
    """
    if spec is None:
        return None
    out: list[Any] = []
    for part in tuple(spec):
        if part is None or isinstance(part, str):
            out.append(part)
        else:
            out.append(list(part))
    return out


# ---------------------------------------------------------------------------
# Leaf I/O


@dataclasses.dataclass(frozen=True)
class LeafInfo:
    """One saved leaf's manifest row.

    ``packed`` marks leaves whose dtype ``.npy`` cannot represent
    natively (bfloat16, float8 -- the ml_dtypes extension types): the
    shard then holds the raw bytes as uint8 with a trailing itemsize
    dim, and ``dtype``/``shape`` record the logical view to rebuild.
    """

    path: str  # tree path ('params/llm/wte')
    file: str  # shard filename within the checkpoint dir
    dtype: str
    shape: tuple[int, ...]
    sha256: str
    spec: list[Any] | None = None  # PartitionSpec metadata (spec_to_meta)
    packed: bool = False

    def to_json(self) -> dict[str, Any]:
        return {
            "path": self.path,
            "file": self.file,
            "dtype": self.dtype,
            "shape": list(self.shape),
            "sha256": self.sha256,
            "spec": self.spec,
            "packed": self.packed,
        }

    @staticmethod
    def from_json(d: dict[str, Any]) -> "LeafInfo":
        return LeafInfo(
            path=d["path"],
            file=d["file"],
            dtype=d["dtype"],
            shape=tuple(d["shape"]),
            sha256=d["sha256"],
            spec=d.get("spec"),
            packed=bool(d.get("packed", False)),
        )


def _resolve_dtype(name: str) -> np.dtype:
    """Logical dtype by name, including ml_dtypes extension types."""
    try:
        return np.dtype(name)
    except TypeError:
        pass
    try:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))
    except (ImportError, AttributeError) as e:
        raise CheckpointCorruptError(
            f"checkpoint leaf dtype {name!r} needs ml_dtypes to restore"
        ) from e


def _leaf_filename(i: int, path: str) -> str:
    tail = re.sub(r"[^A-Za-z0-9_.-]+", "_", path)[-80:]
    return f"leaf_{i:05d}_{tail}.npy"


def _npy_bytes(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_file(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


# ---------------------------------------------------------------------------
# Save / load


def save_pytree(
    path: str,
    tree: Any,
    *,
    specs: Any = None,
    extras: dict[str, Any] | None = None,
    meta: dict[str, Any] | None = None,
) -> str:
    """Atomically write ``tree`` as a checkpoint directory at ``path``.

    ``specs`` (optional) is a pytree of ``PartitionSpec``-likes congruent
    with (a prefix of) ``tree``; each leaf's spec is recorded in the
    manifest so an elastic restore can re-shard host-side.  ``extras`` is
    a JSON blob restored verbatim; ``meta`` adds top-level manifest keys
    (step, wall time, ...).  Returns the committed path.
    """
    final = os.path.abspath(path)
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    spec_by_path: dict[str, Any] = {}
    if specs is not None:
        spec_by_path = dict(_flatten(specs))
    leaves: list[LeafInfo] = []
    for i, (leaf_path, value) in enumerate(_flatten(tree)):
        arr = np.asarray(value)
        # .npy cannot represent ml_dtypes extension types (bfloat16,
        # float8...): store their raw bytes and the logical view.
        packed = arr.dtype.kind == "V"
        stored = arr.view((np.uint8, (arr.dtype.itemsize,))) if packed else arr
        data = _npy_bytes(stored)
        fname = _leaf_filename(i, leaf_path)
        _write_file(os.path.join(tmp, fname), data)
        leaves.append(
            LeafInfo(
                path=leaf_path,
                file=fname,
                dtype=arr.dtype.name if packed else str(arr.dtype),
                shape=tuple(arr.shape),
                sha256=hashlib.sha256(data).hexdigest(),
                spec=spec_to_meta(spec_by_path.get(leaf_path)),
                packed=packed,
            )
        )
    manifest = {
        "format_version": FORMAT_VERSION,
        **(meta or {}),
        "tree": _skeleton(tree),
        "leaves": [leaf.to_json() for leaf in leaves],
        "extras": extras or {},
    }
    payload = json.dumps(manifest, indent=1, sort_keys=False).encode()
    _write_file(os.path.join(tmp, MANIFEST), payload)
    _fsync_dir(tmp)
    # Overwrite via rename-swap, not rmtree-then-rename: the previously
    # committed checkpoint is moved aside (a cheap rename) so the crash
    # window between losing the old name and committing the new one is
    # two metadata operations, with the old payload still on disk under
    # ``.old`` until the new one is in place.
    old = None
    if os.path.exists(final):
        old = final + ".old"
        if os.path.exists(old):
            shutil.rmtree(old)
        os.rename(final, old)
    os.rename(tmp, final)
    _fsync_dir(os.path.dirname(final) or ".")
    if old is not None:
        shutil.rmtree(old, ignore_errors=True)
    return final


def load_manifest(path: str) -> dict[str, Any]:
    mpath = os.path.join(path, MANIFEST)
    if not os.path.isfile(mpath):
        raise CheckpointCorruptError(f"{path}: missing {MANIFEST}")
    try:
        with open(mpath, "rb") as f:
            manifest = json.load(f)
    except (json.JSONDecodeError, OSError) as e:
        raise CheckpointCorruptError(f"{path}: unreadable manifest: {e}") from e
    if manifest.get("format_version") != FORMAT_VERSION:
        raise CheckpointCorruptError(
            f"{path}: format_version {manifest.get('format_version')!r} "
            f"!= {FORMAT_VERSION}"
        )
    return manifest


def load_pytree(path: str, *, verify: bool = True) -> tuple[Any, dict[str, Any]]:
    """Load a checkpoint directory -> (tree, manifest).

    With ``verify`` every shard's SHA-256 is recomputed and compared to
    the manifest; any mismatch (truncated file, bit rot, missing shard)
    raises :class:`CheckpointCorruptError`.
    """
    manifest = load_manifest(path)
    leaves: dict[str, np.ndarray] = {}
    for row in manifest["leaves"]:
        info = LeafInfo.from_json(row)
        fpath = os.path.join(path, info.file)
        if not os.path.isfile(fpath):
            raise CheckpointCorruptError(f"{path}: missing shard {info.file}")
        with open(fpath, "rb") as f:
            data = f.read()
        if verify and hashlib.sha256(data).hexdigest() != info.sha256:
            raise CheckpointCorruptError(
                f"{path}: shard {info.file} failed content hash "
                f"(truncated or corrupt)"
            )
        try:
            arr = np.load(io.BytesIO(data), allow_pickle=False)
        except ValueError as e:
            raise CheckpointCorruptError(
                f"{path}: shard {info.file} is not a readable .npy: {e}"
            ) from e
        if info.packed:
            logical = _resolve_dtype(info.dtype)
            expect = tuple(info.shape) + (logical.itemsize,)
            if arr.dtype != np.uint8 or tuple(arr.shape) != expect:
                raise CheckpointCorruptError(
                    f"{path}: packed shard {info.file} is "
                    f"{arr.dtype}{arr.shape}, expected uint8{expect}"
                )
            arr = arr.view(logical)[..., 0]
        if str(arr.dtype) != info.dtype or tuple(arr.shape) != info.shape:
            raise CheckpointCorruptError(
                f"{path}: shard {info.file} is {arr.dtype}{arr.shape}, "
                f"manifest says {info.dtype}{info.shape}"
            )
        leaves[info.path] = arr
    try:
        tree = _unskeleton(manifest["tree"], "", leaves)
    except KeyError as e:
        raise CheckpointCorruptError(
            f"{path}: manifest/shard mismatch: missing leaf {e}"
        ) from e
    return tree, manifest


# ---------------------------------------------------------------------------
# Step-numbered checkpoint directory with retention + fallback restore


@dataclasses.dataclass
class CheckpointOp:
    """One timed save/restore operation (observability attribution).

    ``wall_ms`` is its duration.  The op log feeds the MFU-gap
    waterfall's ``checkpoint_stall`` component; on the profiler's clock
    each op is a ``ckpt.save`` / ``ckpt.restore`` span
    (:mod:`repro.obs.spans`).
    """

    kind: str  # "save" | "restore"
    step: int  # checkpoint step (-1 when a restore found nothing)
    wall_ms: float


class CheckpointManager:
    """``<root>/step_NNNNNN`` checkpoints with keep-last-K retention.

    ``save`` commits atomically and prunes; ``restore_latest`` walks
    committed checkpoints newest-first, *flags* any corrupt one by
    renaming it to ``step_NNNNNN.corrupt`` and falls back to the next
    older complete checkpoint.  ``.tmp`` directories (crash litter) are
    ignored by :meth:`steps` and removed on the next save.

    Every save/restore is timed into :attr:`ops` (and, when a
    ``metrics`` registry is attached, a ``ckpt_op_ms{op=...}``
    histogram) so checkpoint stalls are attributable instead of
    vanishing into the step time.
    """

    def __init__(self, root: str, *, keep_last: int = 3, metrics=None) -> None:
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        self.root = os.path.abspath(root)
        self.keep_last = keep_last
        self.ops: list[CheckpointOp] = []
        self._h_op = None
        if metrics is not None:
            self._h_op = metrics.histogram(
                "ckpt_op_ms", "checkpoint save/restore wall time", labels=("op",)
            )
        os.makedirs(self.root, exist_ok=True)

    def _record_op(self, kind: str, step: int, t0: float) -> None:
        wall_ms = (time.perf_counter() - t0) * 1e3
        self.ops.append(CheckpointOp(kind=kind, step=step, wall_ms=wall_ms))
        if self._h_op is not None:
            self._h_op.observe(wall_ms, op=kind)

    @property
    def last_op_ms(self) -> float:
        """Duration of the most recent save/restore (0 when none ran)."""
        return self.ops[-1].wall_ms if self.ops else 0.0

    # -- layout ---------------------------------------------------------
    def step_path(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:06d}")

    def steps(self) -> list[int]:
        """Committed checkpoint steps, ascending (tmp/corrupt excluded)."""
        out = []
        for name in os.listdir(self.root):
            m = _STEP_RE.match(name)
            if m and os.path.isdir(os.path.join(self.root, name)):
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    # -- save -----------------------------------------------------------
    def save(
        self,
        step: int,
        tree: Any,
        *,
        specs: Any = None,
        extras: dict[str, Any] | None = None,
        meta: dict[str, Any] | None = None,
    ) -> str:
        t0 = time.perf_counter()
        with span("ckpt.save", step=int(step)):
            self._collect_tmp_litter()
            path = save_pytree(
                self.step_path(step),
                tree,
                specs=specs,
                extras=extras,
                meta={"step": int(step), **(meta or {})},
            )
            self._prune()
        self._record_op("save", int(step), t0)
        return path

    def _collect_tmp_litter(self) -> None:
        for name in os.listdir(self.root):
            if name.endswith((".tmp", ".old")):
                shutil.rmtree(os.path.join(self.root, name), ignore_errors=True)

    def _prune(self) -> None:
        steps = self.steps()
        for s in steps[: max(0, len(steps) - self.keep_last)]:
            shutil.rmtree(self.step_path(s), ignore_errors=True)

    # -- restore --------------------------------------------------------
    def restore(self, step: int, *, verify: bool = True):
        t0 = time.perf_counter()
        try:
            with span("ckpt.restore", step=int(step)):
                return load_pytree(self.step_path(step), verify=verify)
        finally:
            self._record_op("restore", int(step), t0)

    def restore_latest(self, *, verify: bool = True, on_corrupt: str = "flag"):
        """Newest complete checkpoint -> (tree, manifest), or ``None``
        when the root holds no restorable checkpoint.

        A corrupt candidate is skipped; with ``on_corrupt='flag'`` it is
        also renamed to ``<name>.corrupt`` so operators (and the crash-
        consistency tests) can see exactly what was rejected.
        """
        if on_corrupt not in ("flag", "ignore"):
            raise ValueError(
                f"on_corrupt must be 'flag' or 'ignore', got {on_corrupt!r}"
            )
        t0 = time.perf_counter()
        restored = -1
        try:
            for step in reversed(self.steps()):
                path = self.step_path(step)
                try:
                    with span("ckpt.restore", step=step):
                        out = load_pytree(path, verify=verify)
                    restored = step
                    return out
                except CheckpointCorruptError:
                    if on_corrupt == "flag":
                        self._flag_corrupt(path)
            return None
        finally:
            self._record_op("restore", restored, t0)

    def _flag_corrupt(self, path: str) -> None:
        """Rename to a unique ``*.corrupt`` name; never let the rename
        itself abort the fallback walk (a step can be re-saved and go
        corrupt again after an earlier flag took the plain name)."""
        target = path + ".corrupt"
        n = 1
        while os.path.exists(target):
            target = f"{path}.corrupt.{n}"
            n += 1
        try:
            os.rename(path, target)
        except OSError:
            pass

    def corrupt_paths(self) -> list[str]:
        """Checkpoints flagged corrupt by :meth:`restore_latest`."""
        return sorted(
            os.path.join(self.root, n)
            for n in os.listdir(self.root)
            if ".corrupt" in n
        )
