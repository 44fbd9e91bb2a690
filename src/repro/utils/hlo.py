"""Post-SPMD HLO analysis: collective-bytes accounting + roofline terms.

``compiled.as_text()`` (optimized HLO, after the SPMD partitioner) contains
the actual collective ops; we sum the output-buffer bytes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
as the per-chip collective traffic proxy (operand ~= output size for these
ops up to the reduce/gather factor).

Hardware constants: TPU v5e-class per the brief —
197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12          # per chip, bf16
HBM_BW = 819e9               # per chip, bytes/s
ICI_BW = 50e9                # per link, bytes/s

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_LINE_RE = re.compile(
    r"=\s+(?P<ty>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _type_bytes(ty: str) -> int:
    total = 0
    for dt, dims in _TYPE_RE.findall(ty):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-op-kind output bytes summed over the module (per-chip view —
    SPMD HLO is the single-device program)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _LINE_RE.finditer(hlo_text):
        op = m.group("op")
        # '-done' duplicates '-start' buffers; count once (start only)
        span = hlo_text[m.start():m.end()]
        if "-done(" in span:
            continue
        out[op] += _type_bytes(m.group("ty"))
        counts[op] += 1
    out["_counts"] = counts
    out["_total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    """cost_analysis() on an SPMD-partitioned module reports the PER-CHIP
    program (verified empirically: a (1024³) matmul sharded 4-way reports
    flops/4), so hlo_flops / hlo_bytes / coll_bytes here are all per-chip;
    the brief's 'HLO_FLOPs / (chips × peak)' is equivalent with global
    flops = per-chip × chips."""
    arch: str
    shape: str
    n_chips: int
    hlo_flops: float             # per-chip
    hlo_bytes: float             # per-chip
    coll_bytes: float            # per-chip collective traffic
    model_flops: float           # global 6·N·D useful compute
    coll_detail: dict = dataclasses.field(default_factory=dict)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.hlo_flops * self.n_chips
        return self.model_flops / total if total else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "chips": self.n_chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes, "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
        }


def analyze(compiled, *, arch: str, shape: str, n_chips: int,
            model_flops: float) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    return Roofline(arch=arch, shape=shape, n_chips=n_chips,
                    hlo_flops=flops, hlo_bytes=byts,
                    coll_bytes=float(coll["_total"]),
                    model_flops=model_flops, coll_detail=coll)
