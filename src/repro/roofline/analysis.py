"""Three-term roofline from compiled dry-run artifacts.

compute    = HLO_FLOPs / peak_FLOPs            (per chip; cost_analysis is
                                                the per-device SPMD program)
memory     = HLO_bytes / HBM_bw
collective = Σ collective operand bytes / ICI_bw

collective bytes are parsed from the post-SPMD optimized HLO
(``compiled.as_text()``): we sum the *output* buffer sizes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float            # FLOP/s per chip
    hbm_bw: float                # bytes/s per chip
    ici_bw: float                # bytes/s per link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s interchip interconnect over 4 links).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "  %x = (f32[128,1024]{1,0}, bf16[8]{0}) all-gather(...)" — capture
# the full result type then the op name.
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _type_bytes(type_str: str) -> int:
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


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-category summed output bytes of collective ops (per device).

    '-start' variants are counted; their '-done' twins carry the same
    buffer and are skipped to avoid double counting.
    """
    out = {k: 0 for k in _COLLECTIVES}
    seen_done = 0
    for m in _OP_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        # skip the -done half of async pairs
        tail = hlo_text[m.end(2):m.end(2) + 6]
        if m.group(0).rstrip("(").endswith("-done"):
            seen_done += 1
            continue
        out[op] += _type_bytes(type_str)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                 # per device
    hlo_bytes: float                 # per device
    coll_bytes: Dict[str, int]       # per device, by category
    model_flops: float               # 6·N·D (global, analytic)
    device_kind: str                 # key into PEAKS
    memory_stats: Optional[Dict] = None
    compile_seconds: float = 0.0

    @property
    def peaks(self) -> Peaks:
        return peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return sum(self.coll_bytes.values()) / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips) — remat/redundancy waste."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def bound_s(self) -> float:
        """Roofline lower bound on step time (max of the three terms)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / achievable step time (higher = better).

        useful-compute time = MODEL_FLOPS / (chips × peak); the step can at
        best take ``bound_s``, so this is the MFU the compiled program could
        reach if it hit its own roofline.
        """
        ideal = self.model_flops / (self.chips * self.peaks.flops_bf16)
        return ideal / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes, "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "memory_stats": self.memory_stats,
            "compile_seconds": self.compile_seconds,
        }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS = 6·N_active·D for train, 2·N_active·D for
    inference steps (D = tokens processed by the step)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence, plus KV-cache attention reads are
    # memory-, not FLOP-, dominated; 2·N·B is the useful matmul work.
    return 2.0 * n * shape.global_batch


def analyze(compiled, *, arch: str, shape_name: str, mesh_name: str,
            chips: int, model_flops: float, device_kind: str,
            compile_seconds: float = 0.0) -> RooflineReport:
    """Roofline terms via the loop-aware HLO analyzer (hlo_parse).

    ``compiled.cost_analysis()`` counts while bodies once — useless for
    scan-over-layers programs — so flops/bytes/collectives come from
    walking the optimized HLO with trip-count multipliers. The raw
    cost_analysis flops are retained in memory_stats for reference.
    """
    from .hlo_parse import analyze_text

    text = compiled.as_text()
    costs = analyze_text(text)
    flops = costs.flops
    byts = costs.bytes
    colls = {k: int(v) for k, v in costs.coll.items()}
    try:
        raw = compiled.cost_analysis()
        raw_flops = float(raw.get("flops", 0.0))
    except Exception:   # pragma: no cover
        raw_flops = 0.0
    try:
        ms = compiled.memory_analysis()
        mem = {
            "argument_bytes": ms.argument_size_in_bytes,
            "output_bytes": ms.output_size_in_bytes,
            "temp_bytes": ms.temp_size_in_bytes,
            "alias_bytes": ms.alias_size_in_bytes,
            "raw_cost_analysis_flops": raw_flops,
        }
    except Exception:  # pragma: no cover - backend without memory stats
        mem = {"raw_cost_analysis_flops": raw_flops}
    return RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts, coll_bytes=colls,
        model_flops=model_flops, device_kind=device_kind, memory_stats=mem,
        compile_seconds=compile_seconds)
