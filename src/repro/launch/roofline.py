"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch x shape x mesh):
  compute_s    = HLO_FLOPs / peak_FLOPs          (per chip)
  memory_s     = HLO_bytes / HBM_bw              (per chip)
  collective_s = collective_bytes / link_bw      (per chip)

``cost_analysis()`` supplies FLOPs / bytes (per device under SPMD).
Collective bytes are NOT in cost_analysis: we parse the compiled HLO and
sum the RESULT buffer sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute / ragged-all-to-all op
(per-device module => per-device bytes).

Hardware model: per-chip peaks in ``HW_PRESETS``, keyed by the
``device_kind`` JAX reports.  ``get_hw(device_kind)`` looks one up and
an unknown device is an error, never a default; ``device_hw()`` resolves
the attached device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

from repro.obs.ledger import projected_mfu, useful_flops_ratio

__all__ = ["HW", "HW_PRESETS", "get_hw", "device_hw", "RooflineReport",
           "collective_bytes", "analyze"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # B/s per chip
    ici_bw: float  # B/s per link
    name: str  # the device_kind JAX reports
    chips: int = 1


# Per-chip peaks by ``device_kind``.  Source: Google Cloud TPU
# documentation, the "System architecture" page of each generation
# (bf16 peak compute, HBM bandwidth, inter-chip interconnect per link).
HW_PRESETS: dict[str, HW] = {
    "TPU v4": HW(peak_flops=275e12, hbm_bw=1228e9, ici_bw=50e9,
                 name="TPU v4"),
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                      name="TPU v5 lite"),
    "TPU v5": HW(peak_flops=459e12, hbm_bw=2765e9, ici_bw=100e9,
                 name="TPU v5"),
    "TPU v6 lite": HW(peak_flops=918e12, hbm_bw=1640e9, ici_bw=100e9,
                      name="TPU v6 lite"),
}


def get_hw(device_kind: str, *, chips: int | None = None) -> HW:
    """Peaks of ``device_kind`` (as ``jax.Device.device_kind`` spells
    it); ``chips`` sets the chip count the roofline divides by.  An
    unknown kind raises ``ValueError``."""
    try:
        hw = HW_PRESETS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r}; known: "
            f"{sorted(HW_PRESETS)}") from None
    if chips is not None:
        hw = dataclasses.replace(hw, chips=chips)
    return hw


def device_hw(device=None) -> HW:
    """Peaks of ``device`` (default: the first JAX device), with the
    process's device count as ``chips``."""
    import jax

    device = device or jax.devices()[0]
    return get_hw(device.device_kind, chips=len(jax.devices()))


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    """Sum byte sizes of every typed shape in a type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per collective kind, summed RESULT bytes (per-device module)."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+)$", line)
        if not m:
            continue
        rhs = m.group(1)
        for kind in _COLLECTIVES:
            # Match the opcode, not substrings of other ops
            # (all-to-all also matches ragged-all-to-all: order matters).
            if re.search(rf"\)\s*{kind}\(", rhs) or re.search(rf"^\(?.*?\s{kind}\(", rhs):
                if kind == "all-to-all" and "ragged-all-to-all" in rhs:
                    continue
                # Result type = everything before the opcode token.
                result_txt = rhs.split(f" {kind}(")[0]
                out[kind] += _shape_bytes(result_txt)
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_global: float
    useful_ratio: float  # MODEL_FLOPS / (HLO_FLOPs * chips)
    memory_per_device: dict[str, Any]
    # Roofline-projected MFU (ledger canonical formula): useful_ratio
    # discounted by the compute fraction of the serial roofline sum.
    mfu_projected: float = 0.0

    def row(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def analyze(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    cost: dict[str, Any],
    hlo_text: str,
    memory: dict[str, Any],
    model_flops_global: float,
    hw: HW,
) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    compute_s = flops / hw.peak_flops
    memory_s = byts / hw.hbm_bw
    collective_s = coll["total"] / hw.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    # Canonical formula lives in the obs ledger (single source of truth
    # with the training-loop accounting).
    useful = useful_flops_ratio(model_flops_global, flops, hw.chips)
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        flops_per_chip=flops,
        bytes_per_chip=byts,
        coll_bytes_per_chip=float(coll["total"]),
        coll_breakdown=coll,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        model_flops_global=model_flops_global,
        useful_ratio=useful,
        memory_per_device=memory,
        mfu_projected=projected_mfu(useful, compute_s, memory_s, collective_s),
    )
