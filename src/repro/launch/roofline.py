"""Roofline-term extraction from compiled dry-run artifacts (§Roofline).

Three terms per (arch x shape x mesh), all in seconds-per-step on the
TARGET hardware (TPU v5e-class constants; this container only compiles):

    compute    = HLO_FLOPs_per_device            / PEAK_FLOPS
    memory     = HLO_bytes_accessed_per_device   / HBM_BW
    collective = wire_bytes_per_device           / ICI_BW

`cost_analysis()` is per-device post-SPMD, so no chip division is needed
(the formula `global / (chips * peak)` is identical). Collective bytes
are NOT in cost_analysis: we parse `compiled.as_text()` (post-partition
HLO, local shapes), classify every collective op, read its replica group
size g, and apply ring-algorithm wire-byte estimates:

    all-reduce      2 * S * (g-1)/g      (reduce-scatter + all-gather)
    all-gather      R * (g-1)/g          (R = gathered result)
    reduce-scatter  R * (g-1)            (R = scattered result, in = R*g)
    all-to-all      S * (g-1)/g
    collective-permute  S

MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference) with N = active params
audits how much compiled compute is "useful" (catches remat waste).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional

# --- target hardware constants (TPU v5e-class, per chip) ---
PEAK_FLOPS = 197e12   # bf16
HBM_BW = 819e9        # bytes/s
ICI_BW = 50e9         # bytes/s per link
HBM_BYTES = 16 * 1024 ** 3

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)|(?P<dtype>\w+)\[(?P<shape>[\d,]*)\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(",
)
_TUPLE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveOp:
    op: str
    bytes_result: int
    group_size: int
    wire_bytes: float
    line: str


def _type_bytes(dtype: str, shape: str) -> int:
    nelem = 1
    if shape.strip():
        for d in shape.split(","):
            nelem *= int(d)
    return nelem * _DTYPE_BYTES.get(dtype, 4)


def _line_result_bytes(line: str) -> int:
    """Sum byte sizes of the result type(s) on an HLO op line.

    For async `-start` ops the result tuple carries (operand, result);
    we halve to avoid double counting."""
    lhs = line.split("=", 1)
    head = lhs[1] if len(lhs) > 1 else line
    # result types end before the op mnemonic
    m = re.search(r"\s(?:all-reduce|all-gather|reduce-scatter|"
                  r"all-to-all|collective-permute)", head)
    typepart = head[: m.start()] if m else head.split("(", 1)[0]
    total = 0
    for dtype, shape in _TUPLE_RE.findall(typepart):
        total += _type_bytes(dtype, shape)
    if "-start" in line and typepart.strip().startswith("("):
        total //= 2
    return total


def _group_size(line: str, world: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, size = int(m.group(1)), int(m.group(2))
        return max(size, 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len([t for t in m.group(1).split(",") if t.strip()]),
                   1)
    return world


def _wire_bytes(op: str, result_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    frac = (g - 1) / g
    if op == "all-reduce":
        return 2.0 * result_bytes * frac
    if op == "all-gather":
        return result_bytes * frac
    if op == "reduce-scatter":
        return result_bytes * (g - 1)
    if op == "all-to-all":
        return result_bytes * frac
    if op == "collective-permute":
        return float(result_bytes)
    return float(result_bytes)


_COMP_START_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_CALL_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    """HLO text -> {computation name: body lines}. Computation headers
    start at column 0 (body ops are indented); this is stable across
    XLA's text formats and robust to nested-paren parameter tuples."""
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ", "}", ")"):
            m = _COMP_START_RE.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if cur is not None and line.startswith(" "):
            comps[cur].append(line)
    return comps


def _trip_count(cond_lines: List[str]) -> int:
    """Loop bound heuristic: the largest integer constant compared in
    the condition computation (jax scans lower to a counted while)."""
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            best = max(best, int(m.group(1)))
    return best


def _comp_multipliers(comps: Dict[str, List[str]]) -> Dict[str, int]:
    """Execution-count multiplier per computation: product of enclosing
    while trip counts (ENTRY = 1). Conservative DFS over the call graph;
    `while` edges multiply by the condition's trip count."""
    entry = None
    for name in comps:
        if "main" in name:
            entry = name
            break
    if entry is None and comps:
        entry = next(iter(comps))
    mult: Dict[str, int] = {}

    def visit(name: str, m: int):
        if name not in comps:
            return
        if mult.get(name, 0) >= m:
            return
        mult[name] = max(mult.get(name, 0), m)
        for line in comps[name]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                visit(cond, m)
                visit(body, m * trips)
                continue
            for cm in _CALL_RE.finditer(line):
                visit(cm.group(1), m)

    if entry:
        visit(entry, 1)
    return mult


def parse_collectives(hlo_text: str, world: int) -> List[CollectiveOp]:
    """Collective ops with wire bytes, scaled by while trip counts
    (HloCostAnalysis-style single-visit accounting undercounts scanned
    loops; see analytic.py docstring)."""
    comps = _split_computations(hlo_text)
    if comps:
        mult = _comp_multipliers(comps)
        items = [(name, line) for name, lines in comps.items()
                 for line in lines]
    else:  # fallback: flat text
        mult = {}
        items = [("", line) for line in hlo_text.splitlines()]
    out = []
    for name, line in items:
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        rb = _line_result_bytes(line)
        g = _group_size(line, world)
        k = mult.get(name, 1)
        out.append(CollectiveOp(
            op=op, bytes_result=rb, group_size=g,
            wire_bytes=_wire_bytes(op, rb, g) * k,
            line=f"x{k} " + line.strip()[:200]))
    return out


def roofline_report(
    compiled,
    *,
    world: int,
    model_flops_global: float,
    analytic_flops_global: Optional[float] = None,
    analytic_bytes_global: Optional[float] = None,
    steps_hint: str = "",
) -> Dict[str, Any]:
    """Assemble the three-term report from a compiled executable.

    compute/memory terms use the ANALYTIC models when provided (XLA's
    cost analysis undercounts scanned loops — analytic.py docstring);
    the raw cost_analysis numbers are kept in the report for reference.
    The collective term is parsed from the partitioned HLO with while
    trip-count scaling.
    """
    ca = compiled.cost_analysis() or {}
    raw_flops_dev = float(ca.get("flops", 0.0))
    raw_bytes_dev = float(ca.get("bytes accessed", 0.0))
    flops_dev = (analytic_flops_global / world
                 if analytic_flops_global else raw_flops_dev)
    bytes_dev = (analytic_bytes_global / world
                 if analytic_bytes_global else raw_bytes_dev)
    colls = parse_collectives(compiled.as_text(), world)
    wire_dev = sum(c.wire_bytes for c in colls)

    by_kind: Dict[str, float] = {}
    for c in colls:
        by_kind[c.op] = by_kind.get(c.op, 0.0) + c.wire_bytes

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_collective = wire_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    bottleneck = max(terms, key=terms.get)
    model_flops_dev = model_flops_global / world
    useful = model_flops_dev / flops_dev if flops_dev else 0.0

    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes",
                                          0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        }
        live = (mem["argument_bytes"] + mem["output_bytes"]
                + mem["temp_bytes"] - mem["alias_bytes"])
        mem["live_bytes"] = live
        mem["fits_hbm"] = bool(live <= HBM_BYTES)
        mem["hbm_frac"] = live / HBM_BYTES

    top = sorted(colls, key=lambda c: -c.wire_bytes)[:8]
    return {
        "world": world,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "raw_hlo_flops_per_device": raw_flops_dev,
        "raw_hlo_bytes_per_device": raw_bytes_dev,
        "wire_bytes_per_device": wire_dev,
        "wire_bytes_by_kind": by_kind,
        "terms_seconds": terms,
        "bottleneck": bottleneck,
        "model_flops_global": model_flops_global,
        "useful_flops_ratio": useful,
        "n_collectives": len(colls),
        "top_collectives": [
            {"op": c.op, "wire_bytes": c.wire_bytes, "group": c.group_size}
            for c in top
        ],
        "memory_analysis": mem,
        "note": steps_hint,
    }


def model_flops(cfg, shape, active_params: int) -> float:
    """MODEL_FLOPS for the cell: 6ND train, 2ND prefill, 2N·B decode."""
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * active_params * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * active_params * tokens
    # decode: one token per sequence (+ attention over the cache, which
    # is O(cache) and not captured by 2ND — reported separately)
    return 2.0 * active_params * shape.batch
