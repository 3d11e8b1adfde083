"""Roofline extraction from a compiled dry-run artifact (no hardware).

Three terms, in seconds, per the assignment:
    compute    = HLO_FLOPs      / (chips × peak_FLOP/s)
    memory     = HLO_bytes      / (chips × HBM_bw)
    collective = collective_B   / (chips × link_bw)

`compiled.cost_analysis()` yields the PER-DEVICE SPMD program's flops/bytes
(XLA compiles one per-device module), so we divide by per-chip peaks
directly; collective bytes are parsed from the post-partitioning HLO text
(`compiled.as_text()`) by summing operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops (also per-device).

Hardware constants come from `PEAKS`, keyed by `device_kind` as JAX
reports it. A kind not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = [
    "HW", "PEAKS", "peaks_for", "collective_bytes", "roofline",
    "RooflineReport", "parse_hlo_collectives",
]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # bytes/s per ICI link


# Published per-chip peaks (Google Cloud documentation, "TPU v5e"): 197
# TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect over 4 links.
PEAKS = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> HW:
    """Peaks of the chip JAX names `device_kind` (`Device.device_kind`)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:  # explicit {{0,1,2,...},{...}} form: first group's member count
        return max(len(m.group(1).split(",")), 1)
    return 1


def parse_hlo_collectives(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective kind: {count, bytes (result sizes), wire_bytes}.

    The CPU HLO printer omits operand types, so sizes come from the result
    shape on the LHS; per-device wire bytes follow the standard ring-
    algorithm volumes over the op's replica group of size g:
        all-gather       out·(g−1)/g      (out is the gathered size)
        all-reduce       2·size·(g−1)/g
        reduce-scatter   out·(g−1)        (input = out·g)
        all-to-all       size·(g−1)/g
        collective-permute  size
    """
    out: Dict[str, Dict[str, float]] = {
        k: {"count": 0, "bytes": 0.0, "wire_bytes": 0.0} for k in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.*?)\s+([\w-]+)\(", line)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        base = op.replace("-start", "").replace("-done", "")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        size = _shape_bytes(result_type)
        g = _group_size(line)
        if base == "all-gather":
            wire = size * (g - 1) / max(g, 1)
        elif base == "all-reduce":
            wire = 2.0 * size * (g - 1) / max(g, 1)
        elif base == "reduce-scatter":
            wire = size * (g - 1)
        elif base == "all-to-all":
            wire = size * (g - 1) / max(g, 1)
        else:  # collective-permute
            wire = size
        out[base]["count"] += 1
        out[base]["bytes"] += size
        out[base]["wire_bytes"] += wire
    return out


def collective_bytes(hlo_text: str) -> float:
    return sum(v["wire_bytes"] for v in parse_hlo_collectives(hlo_text).values())


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collectives: Dict[str, Dict[str, float]]
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops: float  # 6·N·D (or 6·N_active·D) global
    useful_flops_ratio: float  # model_flops / (flops_per_device × chips)
    chips: int
    memory_per_device: Optional[dict] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline(
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cost: dict,
    hlo_text: str,
    model_flops: float,
    hw: HW,
    memory_per_device: Optional[dict] = None,
) -> RooflineReport:
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    colls = parse_hlo_collectives(hlo_text)
    cbytes = sum(v["wire_bytes"] for v in colls.values())
    t_c = flops / hw.peak_flops
    t_m = bytes_ / hw.hbm_bw
    t_x = cbytes / hw.ici_bw
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)), key=lambda kv: kv[1])[0]
    total_flops = flops * chips
    return RooflineReport(
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        flops_per_device=flops,
        bytes_per_device=bytes_,
        collective_bytes_per_device=cbytes,
        collectives={k: v for k, v in colls.items() if v["count"]},
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        dominant=dom,
        model_flops=model_flops,
        useful_flops_ratio=(model_flops / total_flops) if total_flops else 0.0,
        chips=chips,
        memory_per_device=memory_per_device,
    )
