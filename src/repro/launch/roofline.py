"""Roofline analysis from compiled HLO.

Terms per (arch × shape × mesh), all per-chip, in seconds:

* compute    = HLO_FLOPs / peak_FLOPs        (cost_analysis is per-device)
* memory     = HLO_bytes / HBM_bw
* collective = collective_bytes / ICI_bw     (parsed from compiled HLO)

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s
per ICI link (we report the per-link worst case: a ring all-gather /
reduce-scatter of N bytes moves ≈ N·(k-1)/k through each link serially,
approximated as N bytes per chip per link).

``collective_bytes`` sums the *output operand* sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute in the
compiled module (output size ≈ bytes a chip must receive — the ring-limit
lower bound).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


__all__ = ["HW", "collective_bytes", "roofline_terms", "model_flops",
           "RooflineResult"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12          # bf16 per chip
    hbm_bw: float = 819e9               # bytes/s
    ici_bw: float = 50e9                # bytes/s per link (worst-case 1 link)
    hbm_per_chip: float = 16e9          # v5e: 16 GB


V5E = HW()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s2": 1, "u2": 1,
}

# e.g.  "bf16[256,1024]{1,0}" or "f32[]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# collective op lines:  "%all-reduce.5 = f32[...] all-reduce(...)", also
# fusions never contain collectives so a line scan is sufficient.
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[\w\[\]{},.\s/]+?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _parse_shape_bytes(text: str) -> int:
    """Sum byte sizes of all shapes in a result-type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_WHILE_RE = re.compile(r"while\(.*?\),\s*condition=%([\w.\-]+),\s*body=%([\w.\-]+)")
_BRANCH_RE = re.compile(r"(?:branch_computations|true_computation|"
                        r"false_computation)=\{?%([\w.\-]+(?:,\s*%[\w.\-]+)*)\}?")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _split_computations(hlo_text: str) -> Dict[str, list]:
    """computation name -> list of body lines."""
    comps: Dict[str, list] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and "->" in line and "{" in line:
            m = _COMP_HEAD_RE.match(line.strip())
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY"):
                    entry = cur
                continue
        if cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                comps[cur].append(line.strip())
    comps["__entry__"] = comps.get(entry, [])
    if entry:
        comps["__entry_name__"] = [entry]  # type: ignore[assignment]
    return comps


def _trip_count(cond_lines: list) -> int:
    """Heuristic: the largest s32 constant in the while condition."""
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # unknown: assume ≥2 participants


def _ring_factor(kind: str, k: int, result_bytes: int) -> float:
    """Bytes received per chip on a ring realization of the collective,
    given the op's per-device *result* bytes."""
    if k <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (k - 1) / k * result_bytes
    if kind == "all-gather":
        return (k - 1) / k * result_bytes          # result = gathered size
    if kind == "reduce-scatter":
        return (k - 1) * result_bytes               # result = one shard
    if kind == "all-to-all":
        return (k - 1) / k * result_bytes
    if kind == "collective-permute":
        return float(result_bytes)
    return float(result_bytes)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-kind *executed* collective traffic (bytes received per chip).

    Walks the computation graph: collectives inside while bodies are
    multiplied by the loop trip count (largest s32 constant in the
    condition — exact for lax.scan lowerings), and ring transfer factors
    convert result sizes into per-chip wire bytes.
    """
    comps = _split_computations(hlo_text)
    entry_name = comps.get("__entry_name__", [None])[0]
    if entry_name is None:
        return {}

    # pass 1: per-computation structure
    mult: Dict[str, float] = {entry_name: 1.0}
    order = [entry_name]
    seen = {entry_name}
    # BFS propagating multipliers through while/conditional references
    i = 0
    while i < len(order):
        name = order[i]
        i += 1
        m = mult.get(name, 0.0)
        for line in comps.get(name, ()):
            w = _WHILE_RE.search(line)
            if w:
                cond, body = w.group(1), w.group(2)
                trips = _trip_count(comps.get(cond, []))
                for target, extra in ((body, trips), (cond, trips + 1)):
                    mult[target] = mult.get(target, 0.0) + m * extra
                    if target not in seen:
                        seen.add(target)
                        order.append(target)
                continue
            b = _BRANCH_RE.search(line)
            if b:
                for target in re.findall(r"%([\w.\-]+)", b.group(0)):
                    mult[target] = mult.get(target, 0.0) + m
                    if target not in seen:
                        seen.add(target)
                        order.append(target)

    out: Dict[str, float] = {}
    for name in seen:
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for line in comps.get(name, ()):
            cm = _COLL_RE.search(line)
            if not cm or "-done(" in line:
                continue
            kind = cm.group(1)
            eq = line.index("=")
            op_idx = line.index(kind + "(") if (kind + "(") in line \
                else line.index(kind)
            result_bytes = _parse_shape_bytes(line[eq + 1:op_idx])
            k = _group_size(line)
            out[kind] = out.get(kind, 0.0) + m * _ring_factor(
                kind, k, result_bytes)
    return out


# ---------------------------------------------------------------------------
# Trip-aware FLOP / HBM-byte accounting
#
# ``compiled.cost_analysis()`` counts every op ONCE, but collectives, dots
# and fusions inside while loops (lax.scan: grad-accum × layer-period ×
# attention blocks) execute trip-count times.  We therefore re-derive both
# terms from the HLO text with the same computation-multiplier walk used
# for collectives: FLOPs from dot ops (result × contraction × 2), HBM bytes
# from top-level op operand+result sizes (fusions read inputs once and
# write outputs once — the roofline-relevant traffic).
# ---------------------------------------------------------------------------

_OP_LINE_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_DOT_RE = re.compile(r"\bdot\(%([\w.\-]+),\s*%([\w.\-]+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
# ops that materialize their result in HBM at the computation level
_MATERIALIZE_RE = re.compile(
    r"\b(fusion|dot|copy|dynamic-update-slice|dynamic-slice|convert|reduce|"
    r"transpose|concatenate|scatter|gather|broadcast|pad|select|add|"
    r"multiply|subtract)\(")


def _first_shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


def _computation_multipliers(comps: Dict[str, list]):
    entry = comps.get("__entry_name__", [None])[0]
    if entry is None:
        return {}, []
    mult: Dict[str, float] = {entry: 1.0}
    order = [entry]
    seen = {entry}
    i = 0
    while i < len(order):
        name = order[i]
        i += 1
        m = mult.get(name, 0.0)
        for line in comps.get(name, ()):
            w = _WHILE_RE.search(line)
            if w:
                cond, body = w.group(1), w.group(2)
                trips = _trip_count(comps.get(cond, []))
                for target, extra in ((body, trips), (cond, trips + 1)):
                    mult[target] = mult.get(target, 0.0) + m * extra
                    if target not in seen:
                        seen.add(target)
                        order.append(target)
                continue
            b = _BRANCH_RE.search(line)
            if b:
                for target in re.findall(r"%([\w.\-]+)", b.group(0)):
                    mult[target] = mult.get(target, 0.0) + m
                    if target not in seen:
                        seen.add(target)
                        order.append(target)
    return mult, list(seen)


def hlo_cost(hlo_text: str):
    """Trip-aware (flops, hbm_bytes) per chip from compiled HLO text."""
    comps = _split_computations(hlo_text)
    mult, seen = _computation_multipliers(comps)
    # global name -> result type string (shapes referenced across comps)
    shapes: Dict[str, str] = {}
    for name in comps:
        if name.startswith("__"):
            continue
        for line in comps[name]:
            om = _OP_LINE_RE.match(line)
            if om:
                shapes[om.group(1)] = om.group(2)

    flops = 0.0
    dot_bytes = 0.0
    for name in seen:
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for line in comps.get(name, ()):
            om = _OP_LINE_RE.match(line)
            if not om:
                continue
            rhs = om.group(2)
            # FLOPs: dot ops (covers matmul/einsum; elementwise is minor)
            dm = _DOT_RE.search(rhs)
            if dm and " dot(" in rhs:
                res_dims = _first_shape_dims(rhs)
                lhs_type = shapes.get(dm.group(1), "")
                rhs_type = shapes.get(dm.group(2), "")
                lhs_dims = _first_shape_dims(lhs_type)
                cm = _CONTRACT_RE.search(rhs)
                contract = 1
                if lhs_dims is not None and cm:
                    for d in (int(x) for x in cm.group(1).split(",") if x):
                        if d < len(lhs_dims):
                            contract *= lhs_dims[d]
                if res_dims is not None:
                    n = 1
                    for d in res_dims:
                        n *= d
                    flops += m * 2.0 * n * contract
                km = _MATERIALIZE_RE.search(rhs)
                res_bytes = _parse_shape_bytes(rhs[: km.start()]) if km else 0
                dot_bytes += m * (res_bytes
                                  + _parse_shape_bytes(lhs_type)
                                  + _parse_shape_bytes(rhs_type))
    # dot-operand traffic is a *lower bound* on HBM bytes (every matmul
    # streams its operands at least once per execution) that correctly
    # scales with loop trip counts — the caller maxes it with XLA's
    # one-execution "bytes accessed".
    return flops, dot_bytes


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for training,
    2·N·D for inference, D = processed tokens."""
    from repro.models import build_model
    from repro.models.params import param_count

    n_total = param_count(build_model(cfg).specs())
    if cfg.num_experts:
        # active params: replace E experts by top-k in the MoE blocks
        moe_frac = (cfg.num_experts - cfg.experts_per_token) / cfg.num_experts
        period = max(1, 1)
        # expert params per layer ≈ 3·d·ff (glu) or 2·d·ff
        mats = 3 if cfg.mlp_type in ("swiglu", "geglu") else 2
        expert_params = cfg.num_layers * cfg.num_experts * mats * \
            cfg.d_model * cfg.d_ff
        n_active = n_total - moe_frac * expert_params
    else:
        n_active = n_total
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens


@dataclasses.dataclass
class RooflineResult:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: Dict[str, int]
    peak_mem_per_chip: float
    model_flops_total: float
    hw: HW = V5E

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / self.hw.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (chips × HLO_FLOPs) — remat/redundancy waste."""
        total_hlo = self.flops_per_chip * self.chips
        return self.model_flops_total / max(total_hlo, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute fraction of the bound: how close the *model* math
        comes to the chip's peak under this program = MFU upper bound."""
        t_model = self.model_flops_total / (self.chips * self.hw.peak_flops)
        return t_model / max(self.bound_time, 1e-30)

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "coll_bytes_per_chip": self.coll_bytes_per_chip,
            "coll_breakdown": self.coll_breakdown,
            "peak_mem_per_chip": self.peak_mem_per_chip,
            "model_flops_total": self.model_flops_total,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "dominant": self.dominant,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def roofline_terms(arch: str, shape_name: str, mesh_name: str, chips: int,
                   cost: Dict, hlo_text: str, peak_mem: float,
                   mf: float) -> RooflineResult:
    coll = collective_bytes(hlo_text)
    coll_total = float(sum(coll.values()))
    return RooflineResult(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        flops_per_chip=float(cost.get("flops", 0.0)),
        bytes_per_chip=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_chip=coll_total,
        coll_breakdown={k: int(v) for k, v in coll.items()},
        peak_mem_per_chip=peak_mem,
        model_flops_total=mf,
    )
