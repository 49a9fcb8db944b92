"""Roofline analysis from compiled (post-SPMD, per-device) HLO text.

Why not ``compiled.cost_analysis()``: XLA's cost analysis counts a while
loop's body ONCE, ignoring the trip count — with scan-over-layers a 95-layer
model reports 1 layer of FLOPs. This walker parses the optimized HLO,
multiplies every computation by its enclosing loops' ``known_trip_count``,
and accounts:

  - dot FLOPs (2 * prod(out_shape) * prod(contracting_sizes)),
  - convolution FLOPs (2 * prod(out) * prod(kernel_spatial) * in_features),
  - HBM bytes at op boundaries (operands + result, fusion-boundary only),
  - collective bytes per op class (all-gather / all-reduce / reduce-scatter
    / all-to-all / collective-permute), operand sizes summed per the
    assignment's definition.

All quantities are PER DEVICE because the HLO is the per-device SPMD
program; roofline terms therefore divide by per-chip peak rates, looked
up in ``PEAKS`` by the device's ``device_kind``. A device missing from
the table has no roofline: ``peaks_for`` raises instead of guessing.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Iterable

__all__ = [
    "HW",
    "PEAKS",
    "HloCost",
    "peaks_for",
    "analyze_hlo",
    "roofline_terms",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # bytes/s per ICI link (serial bottleneck model)


# Per-chip peaks keyed by ``jax.Device.device_kind``.
# "TPU v5 lite" is the TPU v5e. Source: Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links
# (50 GB/s per link).
PEAKS: dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> HW:
    """The peak table's entry for ``device_kind``; KeyError when the
    device is not in ``PEAKS`` (no roofline is better than a wrong one)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)
_FREE_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def _shape_info(type_str: str) -> tuple[int, int]:
    """-> (total_bytes, n_elements) over all array components of the type."""
    total_b = total_e = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total_e += n
        total_b += n * _DTYPE_BYTES[dt]
    return total_b, total_e


def _dims_of(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",")]


@dataclasses.dataclass
class _CompStats:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    # (callee, multiplier) edges
    calls: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes_accessed: float
    collective_bytes: dict  # per collective class
    n_collectives: int

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    def to_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": dict(self.collective_bytes),
            "total_collective_bytes": self.total_collective_bytes,
        }


_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^()]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\((.*)$"
)
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLED_RE = re.compile(r"(?:calls|body|to_apply|condition)=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def analyze_hlo(text: str) -> HloCost:
    comps: dict[str, _CompStats] = {}
    entry: str | None = None
    cur: _CompStats | None = None
    cur_name = None
    symbols: dict[str, str] = {}

    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        hdr = _COMP_HDR_RE.match(line)
        if hdr and line.endswith("{"):
            cur_name = hdr.group(1)
            cur = _CompStats()
            comps[cur_name] = cur
            symbols = {}
            if line.startswith("ENTRY"):
                entry = cur_name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        name, type_str, opcode, rest = m.groups()
        symbols[name] = type_str
        if opcode in _FREE_OPS:
            continue

        out_bytes, out_elems = _shape_info(type_str)
        # operand shapes from symbol table (first paren group only)
        depth = 0
        args_str = ""
        for ch in rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    break
                depth -= 1
            args_str += ch
        operand_names = _OPERAND_RE.findall(args_str)
        operand_types = [symbols.get(o, "") for o in operand_names]
        op_bytes = sum(_shape_info(t)[0] for t in operand_types)

        # collectives
        is_coll = False
        for coll in _COLLECTIVES:
            if opcode == coll or opcode == coll + "-start":
                cur.collective_bytes[coll] = cur.collective_bytes.get(
                    coll, 0.0
                ) + max(op_bytes, out_bytes if coll == "all-gather" else 0)
                is_coll = True
            elif opcode == coll + "-done":
                is_coll = True  # counted at -start
        if not is_coll:
            cur.bytes_accessed += out_bytes + op_bytes

        if opcode == "dot":
            lc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
            lhs_dims = _dims_of(operand_types[0]) if operand_types else []
            contract = 1
            if lc and lc.group(1) and lhs_dims:
                for d in lc.group(1).split(","):
                    contract *= lhs_dims[int(d)]
            cur.flops += 2.0 * out_elems * contract
        elif opcode == "convolution":
            rhs_dims = _dims_of(operand_types[1]) if len(operand_types) > 1 else []
            kernel = 1
            for d in rhs_dims[:-1]:
                kernel *= d
            cur.flops += 2.0 * out_elems * kernel

        if opcode in ("while", "fusion", "call", "conditional", "custom-call"):
            trip = 1
            tm = _TRIP_RE.search(line)
            if tm:
                trip = int(tm.group(1))
            # while: body (and condition, negligible) run `trip` times;
            # fusion/call/conditional have no trip_count -> mult 1.
            # Fusion-internal ops never touch HBM: count their dots'
            # FLOPs but not their bytes (the fusion op itself already
            # contributed its boundary bytes above).
            is_fusion = opcode == "fusion"
            for callee in _CALLED_RE.findall(line):
                cur.calls.append((callee, trip, is_fusion))

    if entry is None:
        raise ValueError("no ENTRY computation found in HLO")

    memo: dict[str, tuple[float, float, dict]] = {}

    def walk(name: str) -> tuple[float, float, dict]:
        if name in memo:
            return memo[name]
        st = comps.get(name)
        if st is None:
            return (0.0, 0.0, {})
        memo[name] = (0.0, 0.0, {})  # cycle guard
        fl, by = st.flops, st.bytes_accessed
        cb = dict(st.collective_bytes)
        for callee, mult, is_fusion in st.calls:
            cfl, cby, ccb = walk(callee)
            fl += mult * cfl
            if not is_fusion:
                by += mult * cby
            for k, v in ccb.items():
                cb[k] = cb.get(k, 0.0) + mult * v
        memo[name] = (fl, by, cb)
        return memo[name]

    fl, by, cb = walk(entry)
    n_coll = sum(1 for c in comps.values() for _ in c.collective_bytes)
    return HloCost(
        flops=fl, bytes_accessed=by, collective_bytes=cb, n_collectives=n_coll
    )


# ----------------------------------------------------------------------
# Roofline terms
# ----------------------------------------------------------------------


def roofline_terms(cost: HloCost, hw: HW) -> dict:
    """Per-device time lower bounds for the three roofline terms."""
    t_c = cost.flops / hw.peak_flops
    t_m = cost.bytes_accessed / hw.hbm_bw
    t_x = cost.total_collective_bytes / hw.ici_bw
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    total = max(t_c, t_m, t_x)
    terms["roofline_fraction_compute"] = t_c / total if total else 0.0
    return terms


# ----------------------------------------------------------------------
# Analytic MODEL_FLOPS (6·N·D, N_active for MoE)
# ----------------------------------------------------------------------


def active_params(cfg) -> int:
    """Matmul parameters touched per token (MoE: shared + top-k routed),
    excluding embeddings/lm-head (per the 6ND convention)."""
    from repro.core.pixelfly import param_count
    from repro.models.layers import AttnSpec, MlpSpec
    from repro.models.moe import MoeSpec
    from repro.models.ssm import SsmSpec

    total = 0
    for g in cfg.layer_groups():
        per_layer = 0
        if g.kind in ("dense", "shared_attn", "moe"):
            a = AttnSpec(cfg)
            per_layer += sum(
                param_count(s) for s in (a.wq, a.wk, a.wv, a.wo)
            )
        if g.kind in ("dense", "shared_attn"):
            d_ff = cfg.d_ff
            if g.kind == "dense" and cfg.family == "moe" and cfg.moe_dense_ff:
                d_ff = cfg.moe_dense_ff
            m = MlpSpec(cfg, d_ff)
            per_layer += sum(param_count(s) for s in (m.wg, m.wu, m.wd))
        if g.kind == "moe":
            spec = MoeSpec(cfg)
            if cfg.sparse:
                pat_gu, rank_gu = spec.sparse_layout(cfg.d_model, spec.d_ff)
                pat_d, rank_d = spec.sparse_layout(spec.d_ff, cfg.d_model)
                per_exp = (
                    2 * (pat_gu.nnz + rank_gu * (cfg.d_model + spec.d_ff))
                    + pat_d.nnz + rank_d * (cfg.d_model + spec.d_ff)
                )
            else:
                per_exp = 3 * cfg.d_model * spec.d_ff
            per_layer += cfg.moe_top_k * per_exp
            if cfg.moe_num_shared:
                m = MlpSpec(cfg, cfg.moe_num_shared * spec.d_ff)
                per_layer += sum(param_count(s) for s in (m.wg, m.wu, m.wd))
            per_layer += cfg.d_model * spec.n_exp  # router
        if g.kind == "ssm":
            s = SsmSpec(cfg)
            per_layer += param_count(s.in_proj) + param_count(s.out_proj)
            per_layer += s.conv_dim * cfg.ssm_conv
        total += per_layer * g.count
    return total


def model_flops(cfg, n_tokens: int, *, backward: bool = True) -> float:
    """6·N_active·D (training) or 2·N_active·D (inference)."""
    mult = 6.0 if backward else 2.0
    return mult * active_params(cfg) * n_tokens
