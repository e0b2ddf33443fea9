"""Roofline-term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs  / (chips x 197e12 FLOP/s)
    memory term     = HLO_bytes  / (chips x 819e9  B/s)
    collective term = Sum(collective operand bytes) / (chips x 50e9 B/s)

FLOPs/bytes come from ``compiled.cost_analysis()``. Collective bytes are
parsed from the optimized HLO text: we sum the *output* shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op (output size is the per-device traffic a ring schedule must move, up to
the (n-1)/n factor, and is robust to parse).

SEMANTICS (verified empirically in this container, jax 0.8 CPU backend):
``cost_analysis()``, ``memory_analysis()`` and the printed HLO all describe
the *partitioned per-device module* — a (16,32)x(32,64) matmul sharded over
8 devices reports 9088 flops (= per-device 8192 + overhead), not the global
65536. The roofline terms therefore use per-chip peak numbers with NO
further division by chip count; ``useful_ratio`` compares global model
FLOPs against hlo_flops x chips.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %x = bf16[16,512,128]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9_\[\]{},./:\- ]+?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind output bytes summed over the module. ``-done``
    ops are skipped (the paired ``-start`` already counted)."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        out[m.group(2)] += _shape_bytes(m.group(1))
    return out


@dataclass
class Roofline:
    name: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: dict = field(default_factory=dict)
    model_flops: float = 0.0
    bytes_per_device: float = 0.0

    @property
    def compute_s(self) -> float:
        # hlo_flops is already per-device (see module docstring)
        return self.hlo_flops / PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def collective_s(self) -> float:
        # per-device collective operand bytes over per-link bandwidth
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """global MODEL_FLOPS / global compiled FLOPs (<1 => remat/redundancy
        waste; >1 => compiled compute is *less* than the dense 2ND estimate,
        e.g. GQA/MLA/SWA savings)."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> dict:
        return {
            "name": self.name, "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "bytes_per_device": self.bytes_per_device,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_ratio": self.useful_ratio,
        }


def analyze(name: str, compiled, chips: int, model_flops: float = 0.0, *,
            cost: dict | None = None, supplement: dict | None = None) -> Roofline:
    """``compiled``: the executable (proof) lowering — memory analysis +
    collective schedule. ``cost``: optional per-device {flops, bytes} from
    the REPRO_COST_MODE unrolled lowering (global/chips). ``supplement``:
    analytic global flops/bytes for non-unrollable time-step scans."""
    if cost is not None:
        flops, byts = cost["flops"], cost["bytes"]
    else:
        ca = compiled.cost_analysis() or {}
        flops = float(ca.get("flops", 0.0))
        byts = float(ca.get("bytes accessed", 0.0))
    if supplement:
        flops += supplement.get("flops", 0.0) / chips
        byts += supplement.get("bytes", 0.0) / chips
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    mem = compiled.memory_analysis()
    per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes)
    return Roofline(name=name, chips=chips, hlo_flops=flops, hlo_bytes=byts,
                    coll_bytes=float(sum(coll.values())), coll_breakdown=coll,
                    model_flops=model_flops, bytes_per_device=per_dev)
