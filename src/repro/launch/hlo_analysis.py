"""While-aware HLO analysis: FLOPs and collective bytes with loop trip counts.

XLA's ``compiled.cost_analysis()`` counts each ``while`` body ONCE — but our
models scan over layers (and the chunked attention scans over chunk pairs),
so raw cost_analysis undercounts a 60-layer model by ~60x.  This module
re-derives per-device totals from the partitioned HLO text:

* computations are parsed into symbol tables (every defining line carries
  its shape),
* ``dot``/``convolution`` FLOPs are computed from output + contracting dims,
* collective payload bytes are taken from instruction output shapes
  (all-reduce counted 2x: ring = reduce-scatter + all-gather),
* the call graph (``body=``, ``condition=``, ``calls=``, ``to_apply=``) is
  walked from ENTRY with multipliers: a while body multiplies by its trip
  count (parsed from the loop-bound constant in its condition computation),
  branches of a conditional contribute their max.

Numbers are per-device (the partitioned module is the per-device program).
"""
from __future__ import annotations

import dataclasses
import math
import re
from functools import lru_cache

__all__ = ["HloStats", "analyze_hlo"]

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_CALL_RE = re.compile(r"(?:body|condition|calls|to_apply)=([^,)\s]+|\{[^}]*\})")
_WHILE_RE = re.compile(r"\bwhile\(")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_COMPARE_RE = re.compile(r"compare\(")


@dataclasses.dataclass
class HloStats:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: dict = dataclasses.field(
        default_factory=lambda: {op: 0.0 for op in _COLLECTIVES})
    collective_counts: dict = dataclasses.field(
        default_factory=lambda: {op: 0.0 for op in _COLLECTIVES})
    warnings: list = dataclasses.field(default_factory=list)

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    def to_json(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": dict(self.collective_bytes),
            "collective_counts": dict(self.collective_counts),
            "total_collective_bytes": self.total_collective_bytes,
            "warnings": self.warnings[:20],
        }


def _first_shape(text: str) -> tuple[str, list[int]] | None:
    m = _SHAPE_RE.search(text)
    if not m:
        return None
    dtype, dims = m.group(1), m.group(2)
    shape = [int(d) for d in dims.split(",") if d]
    return dtype, shape


def _all_shapes_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _split_computations(txt: str) -> dict[str, list[str]]:
    """name -> list of body lines (including the header line)."""
    comps: dict[str, list[str]] = {}
    cur_name = None
    cur: list[str] = []
    for line in txt.splitlines():
        stripped = line.rstrip()
        if cur_name is None:
            m = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{$", stripped)
            if m:
                cur_name = m.group(1)
                cur = [stripped]
                if stripped.startswith("ENTRY") or " ENTRY " in stripped:
                    comps["__entry__"] = cur
        else:
            cur.append(stripped)
            if stripped == "}":
                comps[cur_name] = cur
                cur_name = None
    return comps


# operand reference: compiled HLO prints `dot(%lhs, %rhs)`, with no inline
# operand types, so shapes come from the computation's symbol table
_OPERAND = r"%?([\w.\-]+)"


def _operand_shape(name: str, symtab) -> list[int] | None:
    """Shape of an operand named in an _OPERAND match, from ``symtab``."""
    entry = symtab.get(name)
    return None if entry is None else entry[1]


def _dot_flops(line: str, symtab: dict[str, tuple[str, list[int]]]) -> float:
    """2 * prod(output) * prod(lhs contracting dims)."""
    out = _first_shape(line.split("=", 1)[1])
    if out is None:
        return 0.0
    _, out_shape = out
    m = re.search(r"\bdot\(\s*" + _OPERAND, line)
    cd = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", line)
    contracted = 1
    if m and cd:
        lhs_shape = _operand_shape(m.group(1), symtab)
        if lhs_shape is not None:
            for d in cd.group(1).split(","):
                if d and int(d) < len(lhs_shape):
                    contracted *= lhs_shape[int(d)]
        else:
            return -1.0   # unresolved operand — caller records a warning
    return 2.0 * math.prod(out_shape or [1]) * contracted


def _conv_flops(line: str, symtab: dict[str, tuple[str, list[int]]]) -> float:
    out = _first_shape(line.split("=", 1)[1])
    if out is None:
        return 0.0
    _, out_shape = out
    m = re.search(r"\bconvolution\(\s*" + _OPERAND + r"\s*,\s*" + _OPERAND,
                  line)
    if not m:
        return 0.0
    rhs_shape = _operand_shape(m.group(2), symtab)
    if rhs_shape is None:
        return -1.0
    # kernel: spatial... x in_ch x out_ch (exact dim order varies; product
    # over all kernel dims / out_ch gives per-output MACs)
    total_kernel = math.prod(rhs_shape or [1])
    out_ch = out_shape[-1] if out_shape else 1
    per_out = max(total_kernel // max(out_ch, 1), 1)
    return 2.0 * math.prod(out_shape or [1]) * per_out


def analyze_hlo(txt: str) -> HloStats:
    comps = _split_computations(txt)
    stats = HloStats()

    # per-computation: symbol table + local costs + callees
    local: dict[str, dict] = {}
    for name, lines in comps.items():
        symtab: dict[str, tuple[str, list[int]]] = {}
        header = lines[0]
        # fusion-style headers carry typed params: (p: f32[2,3], q: s32[])
        for pm in re.finditer(r"%?([\w.\-]+)\s*:\s*([a-z0-9]+\[[0-9,]*\])",
                              header):
            sh = _first_shape(pm.group(2))
            if sh:
                symtab[pm.group(1)] = sh
        for line in lines[1:]:
            dm = _DEF_RE.match(line)
            if dm:
                sh = _first_shape(dm.group(2))
                if sh:
                    symtab[dm.group(1)] = sh

        flops = 0.0
        hbm = 0.0
        coll_b = {op: 0.0 for op in _COLLECTIVES}
        coll_c = {op: 0.0 for op in _COLLECTIVES}
        callees: list[tuple[str, str]] = []   # (callee, relation)
        whiles: list[tuple[str, str]] = []    # (body, condition)
        for line in lines[1:]:
            # HBM traffic proxy: output + resolved-operand bytes of every
            # top-level op that actually touches memory (fusion internals are
            # registers; shape-only ops are free)
            dm0 = _DEF_RE.match(line)
            if dm0 and not any(
                    f" {skip}(" in line for skip in
                    ("get-tuple-element", "tuple", "parameter", "constant",
                     "bitcast", "after-all", "iota")):
                rhs = dm0.group(2)
                out_sh = _first_shape(rhs)
                if out_sh and out_sh[0] in _DTYPE_BYTES:
                    hbm += math.prod(out_sh[1] or [1]) * _DTYPE_BYTES[out_sh[0]]
                for opm in re.finditer(
                        r"[(,]\s*(?:([a-z0-9]+)\[([0-9,]*)\]"
                        r"(?:\{[^}]*\})?\s+)?%([\w.\-]+)", rhs):
                    dtype, dims, opname = opm.groups()
                    if dtype is None:
                        osh = symtab.get(opname)
                    else:
                        osh = (dtype, [int(d) for d in dims.split(",") if d])
                    if osh is not None and osh[0] in _DTYPE_BYTES:
                        hbm += math.prod(osh[1] or [1]) * _DTYPE_BYTES[osh[0]]
            if " dot(" in line:
                f = _dot_flops(line, symtab)
                if f < 0:
                    stats.warnings.append(f"unresolved dot operand in {name}")
                else:
                    flops += f
            elif " convolution(" in line:
                f = _conv_flops(line, symtab)
                if f < 0:
                    stats.warnings.append(f"unresolved conv operand in {name}")
                else:
                    flops += f
            for op in _COLLECTIVES:
                if f" {op}(" in line or f" {op}-start(" in line:
                    rhs = line.split("=", 1)[1] if "=" in line else line
                    head = rhs.split(op)[0]
                    b = _all_shapes_bytes(head)
                    mult = 2.0 if op == "all-reduce" else 1.0
                    coll_b[op] += b * mult
                    coll_c[op] += 1
            if _WHILE_RE.search(line):
                body = re.search(r"body=%?([\w.\-]+)", line)
                cond = re.search(r"condition=%?([\w.\-]+)", line)
                if body and cond:
                    whiles.append((body.group(1), cond.group(1)))
            else:
                for cm in _CALL_RE.finditer(line):
                    target = cm.group(1)
                    if target.startswith("{"):
                        for t in re.findall(r"%?([\w.\-]+)", target):
                            callees.append((t, "branch"))
                    else:
                        callees.append((target.lstrip("%"), "call"))
        local[name] = {"flops": flops, "hbm": hbm, "coll_b": coll_b,
                       "coll_c": coll_c, "callees": callees, "whiles": whiles}

    def trip_count(cond_name: str) -> float:
        cond = comps.get(cond_name)
        if cond is None:
            return 1.0
        consts = [int(c) for line in cond for c in _CONST_RE.findall(line)]
        if consts:
            # loop bound constant (conditions are tiny: iv < N, or a fused
            # wrapped_compare against N) — max int constant is the bound
            return float(max(consts))
        stats.warnings.append(f"no trip count for condition {cond_name}")
        return 1.0

    memo: dict[str, tuple] = {}

    def walk(name: str, depth: int = 0):
        if name in memo:
            return memo[name]
        if name not in local or depth > 64:
            return (0.0, 0.0, {op: 0.0 for op in _COLLECTIVES},
                    {op: 0.0 for op in _COLLECTIVES})
        lc = local[name]
        flops = lc["flops"]
        hbm = lc["hbm"]
        cb = dict(lc["coll_b"])
        cc = dict(lc["coll_c"])
        branch_best = None
        for callee, rel in lc["callees"]:
            sub = walk(callee, depth + 1)
            if rel == "branch":
                if branch_best is None or sub[0] > branch_best[0]:
                    branch_best = sub
            else:
                flops += sub[0]
                hbm += sub[1]
                for op in _COLLECTIVES:
                    cb[op] += sub[2][op]
                    cc[op] += sub[3][op]
        if branch_best is not None:
            flops += branch_best[0]
            hbm += branch_best[1]
            for op in _COLLECTIVES:
                cb[op] += branch_best[2][op]
                cc[op] += branch_best[3][op]
        for body, cond in lc["whiles"]:
            n = trip_count(cond)
            sub = walk(body, depth + 1)
            flops += n * sub[0]
            hbm += n * sub[1]
            for op in _COLLECTIVES:
                cb[op] += n * sub[2][op]
                cc[op] += n * sub[3][op]
        memo[name] = (flops, hbm, cb, cc)
        return memo[name]

    entry = None
    for name, lines in comps.items():
        if lines and ("ENTRY" in lines[0]):
            entry = name
            break
    if entry is None:
        # fall back: computation with the most instructions
        entry = max(comps, key=lambda n: len(comps[n]))
        stats.warnings.append("no ENTRY found; using largest computation")

    flops, hbm, cb, cc = walk(entry)
    stats.flops = flops
    stats.hbm_bytes = hbm
    stats.collective_bytes = cb
    stats.collective_counts = cc
    return stats
