"""Roofline analysis from the compiled dry-run artifact.

Three terms per (arch x shape x mesh) cell, all in seconds:

    compute    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
    memory     = HLO_bytes / (chips x peak HBM B/s)
    collective = collective_bytes / (chips x ICI B/s per link)

with the peaks of the target chip looked up in `repro.hw.PEAKS` by its
``device_kind`` (the dry run's production meshes describe TPU v5e).

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis(). collective_bytes is
parsed from the post-SPMD optimized HLO text: for every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op we sum the
operand sizes (a name->shape table is built from the instruction defs, so
operand sizes are exact, not guessed from the output shape).

MODEL_FLOPS is the analytic useful-work number (6·N·D train, 2·N·D forward,
per the assignment: N_active for MoE); its ratio against HLO_FLOPs exposes
remat recompute and routing/dispatch waste.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

from repro import hw
from repro.configs.base import ModelConfig, ShapeConfig

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# "%name = <shape-or-tuple> opcode(...)" — instruction definition
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[\w\[\],{}:\s]+?)\s+"
    r"([\w\-]+)\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-kind operand bytes + op counts from optimized HLO."""
    shapes: Dict[str, str] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _INSTR_RE.match(ln)
        if m:
            shapes[m.group(1)] = m.group(2)
    stats = {k: {"count": 0, "operand_bytes": 0, "output_bytes": 0}
             for k in _COLLECTIVES}
    for ln in lines:
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        name, out_shape, op = m.groups()
        kind = next((k for k in _COLLECTIVES
                     if op == k or op.startswith(k + "-")), None)
        if kind is None:
            continue
        # operand list: %arg names inside the call parens
        call = ln[ln.index(op + "(") + len(op) + 1:]
        depth = 1
        args = ""
        for ch in call:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            args += ch
        op_bytes = 0
        for ref in re.findall(r"%?([\w.\-]+)", args):
            if ref in shapes:
                op_bytes += _shape_bytes(shapes[ref])
        stats[kind]["count"] += 1
        stats[kind]["operand_bytes"] += op_bytes
        stats[kind]["output_bytes"] += _shape_bytes(out_shape)
    return stats


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N·D (train) / 2·N·D (fwd) with N = active params, D = tokens.
    For enc-dec the encoder weights see only the frame tokens, so N·D splits
    into N_dec·T_text + N_enc·T_frames (otherwise seamless would report a
    'useful ratio' > 1)."""
    n = cfg.param_count(active_only=(cfg.family == "moe"))
    mult = 6.0 if shape.kind == "train" else 2.0
    if shape.kind == "decode":
        return mult * n * shape.global_batch
    t_text = shape.global_batch * shape.seq_len
    if cfg.family == "encdec":
        D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
        attn = D * H * hd + 2 * D * KV * hd + H * hd * D
        mlp = (3 if cfg.mlp_kind == "swiglu" else 2) * D * cfg.d_ff
        n_enc = cfg.n_enc_layers * (attn + mlp)
        t_frames = shape.global_batch * cfg.n_frontend_tokens
        return mult * ((n - n_enc) * t_text + n_enc * t_frames)
    return mult * n * t_text


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collective_by_kind: Dict[str, Dict[str, float]]
    model_flops_: float
    peaks: hw.ChipPeaks
    bytes_per_device: Optional[float] = None
    dot_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * self.peaks.flops_bf16)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def t_memory_floor(self) -> float:
        """Matmul-attributed traffic only — fusion-granularity independent
        (XLA:CPU fuses less than TPU; the true TPU memory term lies between
        t_memory_floor and t_memory)."""
        return self.dot_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * self.peaks.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops_ / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-term bound that is useful model compute:
        (MODEL_FLOPS / chips / peak) / max(term). 1.0 = the step takes
        exactly as long as the useful flops at peak — the roofline."""
        t_use = self.model_flops_ / (self.chips * self.peaks.flops_bf16)
        t_step = max(self.t_compute, self.t_memory, self.t_collective)
        return t_use / t_step if t_step else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_by_kind": self.collective_by_kind,
            "model_flops": self.model_flops_,
            "bytes_per_device": self.bytes_per_device,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_memory_floor_s": self.t_memory_floor,
            "dot_bytes": self.dot_bytes,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(compiled, cfg: ModelConfig, shape: ShapeConfig, mesh_name: str,
            chips: int, arch: str, device_kind: str) -> Roofline:
    """NOTE: XLA's cost_analysis() counts while (scan) bodies once — useless
    for scan-over-layers models — so FLOPs/bytes/collective-bytes come from
    the trip-count-aware walker in launch/hlocost.py (validated against XLA
    on unrolled modules in tests/test_hlocost.py). Costs are per-partition;
    the roofline terms below are therefore per-chip by construction, and the
    assignment's "/ chips" is already applied by SPMD partitioning."""
    from repro.launch.hlocost import analyze_text
    text = compiled.as_text()
    cost = analyze_text(text)
    # per-chip numbers (post-SPMD module) -> keep terms per chip
    flops = cost.flops * chips          # global, for reporting
    byts = cost.bytes * chips
    dot_bytes = cost.dot_bytes * chips
    coll_bytes = cost.collective_bytes * chips
    # per-kind op counts (trip-count multiplied, per chip)
    coll = {k: {"count": v} for k, v in cost.collective_ops.items()}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = (getattr(ma, "argument_size_in_bytes", 0)
                   + getattr(ma, "output_size_in_bytes", 0)
                   + getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
                    hlo_flops=flops, hlo_bytes=byts,
                    collective_bytes=coll_bytes, collective_by_kind=coll,
                    model_flops_=model_flops(cfg, shape),
                    peaks=hw.peaks(device_kind),
                    bytes_per_device=mem, dot_bytes=dot_bytes)
