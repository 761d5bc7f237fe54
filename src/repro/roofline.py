"""Per-chip peaks, and roofline terms from compiled dry-run artifacts.

    compute    = device_flops / peak_flops
    memory     = device_bytes / hbm_bw
    collective = device_collective_bytes / link_bw

``cost_analysis`` on an SPMD-partitioned executable reports *per-device*
FLOPs/bytes, and collective operand shapes in the partitioned HLO are
per-device too, so each term divides by a single chip's peak -- equivalent
to the global-totals/(chips x peak) formulation.

Collective bytes are NOT in cost_analysis: we parse the optimized HLO and
sum operand sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (async *-start counted once, *-done skipped).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""
    flops: float               # bf16 FLOP/s
    int8_ops: float            # int8 OP/s
    hbm_bw: float              # HBM bytes/s
    hbm_bytes: float           # HBM capacity
    ici_link_bw: float         # chip-to-chip bytes/s per link
    source: str


#: keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, int8_ops=393e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e" (per chip)'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a chip not in ``PEAKS`` raises (a
    roofline share against another chip's peaks would be wrong)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

# shape like bf16[2,1024,8192]
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# "  %name = TYPE[...] opcode(...)," -- capture opcode and the operand text
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z0-9-]+)(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device operand bytes of every collective in optimized HLO."""
    bytes_by = {k: 0 for k in _COLLECTIVES}
    count_by = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-start"):
            op = op[:-6]
        if op not in _COLLECTIVES:
            continue
        # operand shapes: everything after the opcode's opening paren
        idx = line.find(op)
        paren = line.find("(", idx)
        operand_text = line[paren:line.rfind(")")]
        shapes = _SHAPE_RE.findall(operand_text)
        if not shapes:  # operands printed without types: fall back to output
            shapes = _SHAPE_RE.findall(line[:line.find("=")]) or \
                _SHAPE_RE.findall(line)
        total = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        bytes_by[op] += total
        count_by[op] += 1
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    flops: float               # per-device
    hbm_bytes: float           # per-device
    collective_bytes: float    # per-device
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)


def roofline_terms(cost: dict, collectives: CollectiveStats,
                   chip: ChipPeaks) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = float(collectives.total_bytes)
    tc = flops / chip.flops
    tm = hbm / chip.hbm_bw
    tx = coll / chip.ici_link_bw
    terms = {"compute": tc, "memory": tm, "collective": tx}
    bottleneck = max(terms, key=terms.get)
    return Roofline(flops, hbm, coll, tc, tm, tx, bottleneck)


def model_flops_utilization(model_flops_per_device: float,
                            roof: Roofline, chip: ChipPeaks) -> dict:
    """MODEL_FLOPS/HLO_FLOPs and the roofline fraction of the dominant term."""
    useful = (model_flops_per_device / roof.flops) if roof.flops else 0.0
    # fraction of roofline: time the useful compute would take at peak over
    # the dominant-term time (how close the cell is to its own roofline)
    t_useful = model_flops_per_device / chip.flops
    frac = t_useful / roof.t_bound if roof.t_bound else 0.0
    return {"useful_flops_ratio": useful, "roofline_fraction": frac}
