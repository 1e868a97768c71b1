"""Roofline analysis of compiled artifacts (TPU v5e model).

Three terms, all in seconds per step, derived from the dry-run's compiled
module (per-device partitioned program):

  compute    = HLO_FLOPs_per_device / PEAK_FLOPS
  memory     = HLO_bytes_per_device / HBM_BW
  collective = collective_bytes_per_device / ICI_BW

IMPORTANT measurement note (verified by probe): ``compiled.cost_analysis()``
counts while-loop bodies ONCE — a scanned 48-layer model would be
undercounted ~50x. This module therefore re-derives FLOPs / bytes /
collective bytes from the compiled HLO text with a symbol table and
**trip-count multiplication** for while loops (trip counts are recovered
from the s32 bound constants that XLA clones into each loop's condition
computation). cost_analysis() is kept as a cross-check on 1-trip modules.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]"
)
_SHAPE_RE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
_OPND_RE = re.compile(r"%([\w\.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)"
)
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CALL_RE = re.compile(r"\b(?:call|async-start)\(")

_ELEMENTWISE = frozenset(
    "add subtract multiply divide exponential tanh maximum minimum select "
    "compare convert negate rsqrt sqrt log and or not xor power abs sign "
    "floor ceil clamp broadcast iota reduce exponential-minus-one".split()
)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict ({} where the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def _dims(dim_str: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in dim_str.split(",")) if dim_str else ()


def _nbytes(dtype: str, dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class HloCosts:
    """Trip-count-aware totals for one compiled (per-device) module."""

    flops: float = 0.0
    bytes_accessed: float = 0.0
    kernel_ref_bytes: float = 0.0  # ref-path traffic the Pallas kernel replaces
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_count: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def collective_bytes(self) -> float:
        return sum(self.coll_bytes.values())

    @property
    def collective_count(self) -> int:
        return int(sum(self.coll_count.values()))

    def describe_collectives(self) -> str:
        rows = [
            f"{op}: {int(self.coll_count.get(op, 0))} ops, "
            f"{self.coll_bytes.get(op, 0)/1e6:.1f} MB"
            for op in COLLECTIVE_OPS
            if self.coll_count.get(op, 0)
        ]
        return "; ".join(rows) if rows else "none"


def parse_hlo_costs(hlo_text: str) -> HloCosts:
    lines = hlo_text.splitlines()

    # ---- pass 1: module-wide symbol table (instruction -> dtype/dims) ------
    symbols: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
    for ln in lines:
        m = _DEF_RE.match(ln)
        if m:
            symbols[m.group(1)] = (m.group(2), _dims(m.group(3)))

    # ---- pass 2: split into computations ----------------------------------
    comps: Dict[str, List[str]] = {}
    entry: Optional[str] = None
    cur: Optional[str] = None
    for ln in lines:
        s = ln.rstrip()
        if cur is None:
            if s.endswith("{") and ("->" in s or s.startswith("ENTRY")):
                name = s.split("(")[0].strip().lstrip("ENTRY ").strip().lstrip("%")
                cur = name
                comps[cur] = []
                if s.startswith("ENTRY"):
                    entry = cur
        else:
            if s.strip() == "}":
                cur = None
            else:
                comps[cur].append(s.strip())
    if entry is None and comps:
        entry = next(iter(comps))

    # ---- logical-bf16 detection --------------------------------------------
    # XLA:CPU's float-normalization materializes logical bf16 values as f32
    # (convert(bf16)->f32 chains). The TPU target keeps them bf16, so
    # collectives fed by such converts are counted at HALF (logical) bytes.
    def _root_convert_from_bf16(comp: str) -> bool:
        body = comps.get(comp, [])
        for ln in body:
            if ln.startswith("ROOT "):
                m = _DEF_RE.match(ln)
                if not m or not m.group(2).startswith("f32"):
                    return False
                if " convert(" not in ln:
                    return False
                src = _OPND_RE.findall(ln.split(" convert(", 1)[1])
                if not src:
                    return False
                # source defined inside this computation
                for l2 in body:
                    m2 = _DEF_RE.match(l2)
                    if m2 and m2.group(1) == src[0]:
                        return m2.group(2) == "bf16"
        return False

    _fusion_root_bf16: Dict[str, bool] = {}
    logical_bf16: set = set()
    _CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
    for ln in lines:
        m = _DEF_RE.match(ln)
        if not m or not m.group(2).startswith("f32"):
            continue
        if " convert(" in ln and " fusion(" not in ln:
            src = _OPND_RE.findall(ln.split(" convert(", 1)[1])
            if src and symbols.get(src[0], ("",))[0] == "bf16":
                logical_bf16.add(m.group(1))
        elif " fusion(" in ln:
            mc = _CALLS_RE.search(ln)
            if mc:
                fc = mc.group(1)
                if fc not in _fusion_root_bf16:
                    _fusion_root_bf16[fc] = _root_convert_from_bf16(fc)
                if _fusion_root_bf16[fc]:
                    logical_bf16.add(m.group(1))

    # ---- per-computation raw costs + while edges ---------------------------
    raw: Dict[str, HloCosts] = {}
    whiles: Dict[str, List[Tuple[str, str]]] = {}
    calls: Dict[str, List[str]] = {}
    for name, body in comps.items():
        hc = HloCosts()
        w: List[Tuple[str, str]] = []
        cl: List[str] = []
        for ln in body:
            mw = _WHILE_RE.search(ln)
            if mw:
                w.append((mw.group(1), mw.group(2)))
            md = _DEF_RE.match(ln)
            out_bytes = 0
            if md:
                out_bytes = _nbytes(md.group(2), _dims(md.group(3)))
            # ---- flops: dot ops -------------------------------------------
            if " dot(" in ln and md:
                out_dims = _dims(md.group(3))
                inside = ln.split(" dot(", 1)[1]
                opnds = _OPND_RE.findall(inside)
                mc = _CDIMS_RE.search(ln)
                if opnds and mc and opnds[0] in symbols:
                    lhs_dims = symbols[opnds[0]][1]
                    k = 1
                    for ci in _dims(mc.group(1)):
                        if ci < len(lhs_dims):
                            k *= lhs_dims[ci]
                    out_n = 1
                    for d in out_dims:
                        out_n *= d
                    hc.flops += 2.0 * out_n * k
            # ---- bytes: fusion-aware accounting ------------------------------
            # tuples/GTE/bitcast are metadata (no traffic); standalone
            # elementwise ops count output only (TPU fuses them with their
            # producer); fusions/dots/copies/DUS count operands + output.
            if md and not any(
                f" {t}(" in ln
                for t in ("tuple", "get-tuple-element", "parameter", "bitcast",
                          "constant")
            ):
                kind = ln.split("=", 1)[1].strip().split("(")[0].split()[-1]
                elementwise = kind in _ELEMENTWISE

                def _opnd_bytes(opnd: str) -> float:
                    b1 = float(_nbytes(*symbols[opnd]))
                    # logically-bf16 values materialized f32 by the CPU
                    # backend count at TPU-target (bf16) size
                    return b1 * 0.5 if opnd in logical_bf16 else b1

                out_b = float(out_bytes)
                if md.group(1) in logical_bf16:
                    out_b *= 0.5
                if kind in ("dynamic-update-slice", "scatter"):
                    # in-place on TPU (donated/aliased): traffic = the update
                    # operand only, not the full buffer
                    argpart = ln.split("(", 1)[1] if "(" in ln else ""
                    opnds = _OPND_RE.findall(argpart)
                    b = 0.0
                    for opnd in opnds[1:2]:
                        if opnd in symbols:
                            b += _opnd_bytes(opnd)
                elif elementwise:
                    b = out_b
                else:
                    b = out_b
                    argpart = ln.split("(", 1)[1] if "(" in ln else ""
                    for opnd in _OPND_RE.findall(argpart)[:8]:
                        if opnd in symbols:
                            b += _opnd_bytes(opnd)
                if "KERNEL_" in ln:
                    # ref-path internals of a Pallas-kernel region: on the TPU
                    # target this traffic stays in VMEM; accounted separately
                    # and replaced by the kernel's streaming bytes.
                    hc.kernel_ref_bytes += b
                else:
                    hc.bytes_accessed += b
            # ---- collectives ----------------------------------------------
            for op in COLLECTIVE_OPS:
                if f" {op}(" in ln or f" {op}-start(" in ln:
                    cb = 0
                    argpart = ln.split("(", 1)[1] if "(" in ln else ""
                    for opnd in _OPND_RE.findall(argpart):
                        if opnd in symbols:
                            b1 = _nbytes(*symbols[opnd])
                            if opnd in logical_bf16:
                                b1 *= 0.5  # CPU f32-materialized bf16 value
                            cb += b1
                    if cb == 0 and md:
                        cb = out_bytes
                    if "_promoted" in ln:
                        # CPU-backend artifact: XLA promotes bf16/f16
                        # reductions to f32 on host ("%add.clone_promoted").
                        # The TPU target reduces at the original dtype —
                        # count the pre-promotion bytes.
                        cb *= 0.5
                    hc.coll_bytes[op] = hc.coll_bytes.get(op, 0.0) + cb
                    hc.coll_count[op] = hc.coll_count.get(op, 0) + 1
                    break
        raw[name] = hc
        whiles[name] = w
        calls[name] = cl

    def trip_count(cond: str) -> int:
        consts = []
        for ln in comps.get(cond, []):
            consts += [int(x) for x in _CONST_RE.findall(ln)]
        return max(consts) if consts else 1

    memo: Dict[str, HloCosts] = {}

    def total(name: str, depth: int = 0) -> HloCosts:
        if name in memo or depth > 24:
            return memo.get(name, HloCosts())
        base = raw.get(name, HloCosts())
        acc = HloCosts(
            flops=base.flops,
            bytes_accessed=base.bytes_accessed,
            kernel_ref_bytes=base.kernel_ref_bytes,
            coll_bytes=dict(base.coll_bytes),
            coll_count=dict(base.coll_count),
        )
        for cond, bodyc in whiles.get(name, []):
            t = trip_count(cond)
            sub = total(bodyc, depth + 1)
            acc.flops += t * sub.flops
            acc.bytes_accessed += t * sub.bytes_accessed
            acc.kernel_ref_bytes += t * sub.kernel_ref_bytes
            for op, v in sub.coll_bytes.items():
                acc.coll_bytes[op] = acc.coll_bytes.get(op, 0.0) + t * v
            for op, v in sub.coll_count.items():
                acc.coll_count[op] = acc.coll_count.get(op, 0) + t * v
        memo[name] = acc
        return acc

    return total(entry) if entry else HloCosts()


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    collective_bytes_per_dev: float
    collective_count: int
    n_devices: int
    model_flops: float  # 6*N*D-style global useful FLOPs
    overlap: float = 0.0  # fraction of collective hidden under compute

    @property
    def t_compute(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        ts = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(ts, key=ts.get)

    @property
    def t_step(self) -> float:
        return max(self.t_compute, self.t_memory) + (
            1.0 - self.overlap
        ) * self.t_collective

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops_per_dev * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """ideal_time / predicted_time, ideal = useful FLOPs at peak."""
        ideal = self.model_flops / (self.n_devices * PEAK_FLOPS)
        return ideal / self.t_step if self.t_step else 0.0

    def row(self) -> Dict[str, object]:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "t_step_s": self.t_step,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collective_count": self.collective_count,
        }


def kernel_hbm_bytes(cfg, shape, model_size: int, dp_size: int,
                     microbatches: int, remat_full: bool = True) -> float:
    """Per-device HBM traffic of the Pallas-kernel regions (the fused TPU
    target), substituted for the reference path's materialized intermediates.

    flash attention fwd: read q,k,v + write o (KV streamed through VMEM);
    bwd ~ 3x fwd; full remat adds one fwd. SSD: read x,B,C,dt + write y.
    Decode: the fused decode-attention reads the KV cache once per step.
    """
    B, S = shape.global_batch, shape.seq_len
    bpe = 2  # bf16
    mult = 1.0 if shape.kind != "train" else (4.0 + (1.0 if remat_full else 0.0))
    total = 0.0
    tokens_dev = max(B // max(dp_size, 1), 1) * S / max(microbatches, 1)

    if cfg.n_heads:
        hd = cfg.resolved_head_dim
        H_loc = max(cfg.n_heads // model_size, 1)
        K_loc = max(cfg.kv_heads // model_size, 1)
        L = (
            cfg.n_layers
            if cfg.family != "hybrid"
            else -(-cfg.n_layers // cfg.hybrid_attn_every)
        )
        if shape.kind == "decode":
            # cache read once (k+v) + q/o negligible
            b_loc = max(B // max(dp_size, 1), 1)
            per_layer = 2 * b_loc * S * K_loc * hd * bpe
            total += L * per_layer
        else:
            per_layer_mb = tokens_dev * (H_loc * 2 + K_loc * 2) * hd * bpe
            total += L * per_layer_mb * microbatches * mult

    if cfg.moe is not None and shape.kind != "decode":
        # moe_permute row-copy kernel: dispatch writes 1.25*Tk rows +
        # reads Tk token rows; combine reads Tk + writes T rows (x read+write
        # on the TPU DMA path)
        rows = tokens_dev * cfg.moe.top_k * 2.25 + tokens_dev
        per_layer_mb = 2.0 * rows * cfg.d_model * bpe
        total += cfg.n_layers * per_layer_mb * microbatches * mult

    if cfg.ssm is not None:
        inner = cfg.ssm.expand * cfg.d_model
        inner_loc = max(inner // model_size, 1)
        N = cfg.ssm.state_dim
        L = cfg.n_layers
        if shape.kind == "decode":
            b_loc = max(B // max(dp_size, 1), 1)
            H_loc = max((inner // cfg.ssm.head_dim) // model_size, 1)
            total += L * b_loc * H_loc * cfg.ssm.head_dim * N * 4 * 2  # state rw
        else:
            per_layer_mb = tokens_dev * (2 * inner_loc + 2 * N) * bpe
            total += L * per_layer_mb * microbatches * mult
    return total


def model_flops(cfg, shape, n_active: Optional[int] = None) -> float:
    """Useful-work FLOPs: 6*N*D train, 2*N*D inference + attention terms."""
    N = n_active if n_active is not None else cfg.n_active_params()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        base = 6.0 * N * B * S
        attn_mult = 3.0  # fwd + 2x bwd
    elif shape.kind == "prefill":
        base = 2.0 * N * B * S
        attn_mult = 1.0
    else:  # decode: one token per sequence
        base = 2.0 * N * B
        attn_mult = 1.0

    attn = 0.0
    if cfg.n_heads:
        hd = cfg.resolved_head_dim
        H = cfg.n_heads
        L = (
            cfg.n_layers
            if cfg.family != "hybrid"
            else -(-cfg.n_layers // cfg.hybrid_attn_every)
        )
        if shape.kind == "decode":
            attn = 4.0 * B * H * hd * S * L
        else:
            causal = 0.5 if cfg.causal else 1.0
            if cfg.local_global_pattern and cfg.local_window < S:
                # half the layers see only the window
                kv_eff = (S + cfg.local_window) / 2
            else:
                kv_eff = S
            attn = 4.0 * B * S * kv_eff * H * hd * L * causal
        attn *= attn_mult
    return base + attn
