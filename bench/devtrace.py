"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData``, and the HLO modules
the trace carries (recorded with ``enable_hlo_proto``) with a small
protobuf reader of its own:

  window      the benchmark's own ``bench.window`` host annotation
  busy        the union of the intervals in which an op ran on a device
              plane (``/device:TPU:<i>``, line ``XLA Ops``), inside the
              window, averaged over the device planes
  op time     device seconds per op, and per ``repro.ops.<kernel>``
              scope: an op belongs to the scope named in its HLO
              instruction's ``op_name`` metadata (or, for a fusion, in
              its fused instructions'), found through the module that
              the ``XLA Modules`` line shows running around it
  idle gaps   the stretches of the window with no op on the device,
              each named by the innermost benchmark annotation
              (``bench.*``) that covers it on the host

Device and host events of one trace share a clock.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
WINDOW = "bench.window"
SCOPE = re.compile(r"repro\.ops\.([A-Za-z0-9_]+)")
TOP = 10


# ------------------------------------------------------------ protobuf
def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if b < 0x80:
                return out
            shift += 7

    while i < n:
        key = varint()
        num, wt = key >> 3, key & 7
        if wt == 0:
            yield num, wt, varint()
        elif wt == 1:
            yield num, wt, buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln = varint()
            yield num, wt, buf[i:i + ln]
            i += ln
        elif wt == 5:
            yield num, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wt} not supported")


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _instruction_scopes(hlo: memoryview) -> Dict[str, Tuple[str, Optional[str]]]:
    """{instruction name: (opcode, kernel scope or None)} of one
    ``HloProto`` (hlo_module=1; module computations=3; computation id=5,
    instructions=2; instruction name=1, opcode=2, metadata=7 (op_name=2),
    called_computation_ids=38). A fusion without a scope of its own
    takes the first scope among its fused instructions."""
    comps: Dict[int, List[Tuple[str, str, Optional[str], List[int]]]] = {}
    for num, _, mod in _fields(hlo):
        if num != 1:
            continue
        for mnum, _, comp in _fields(mod):
            if mnum != 3:
                continue
            cid, instrs = None, []
            for cnum, _, cv in _fields(comp):
                if cnum == 5:
                    cid = cv
                elif cnum == 2:
                    name, opcode, scope, called = "", "", None, []
                    for inum, iwt, iv in _fields(cv):
                        if inum == 1:
                            name = _text(iv)
                        elif inum == 2:
                            opcode = _text(iv)
                        elif inum == 7:
                            for onum, _, ov in _fields(iv):
                                if onum == 2:
                                    m = SCOPE.search(_text(ov))
                                    scope = m.group(1) if m else None
                        elif inum == 38:
                            if iwt == 0:
                                called.append(iv)
                            else:
                                called.extend(v for _, _, v in
                                              _packed_varints(iv))
                    instrs.append((name, opcode, scope, called))
            comps[cid] = instrs
    out: Dict[str, Tuple[str, Optional[str]]] = {}
    for instrs in comps.values():
        for name, opcode, scope, called in instrs:
            if scope is None and opcode == "fusion":
                for c in called:
                    scope = next((s for _, _, s, _ in comps.get(c, ())
                                  if s is not None), None)
                    if scope is not None:
                        break
            out[name] = (opcode, scope)
    return out


def _packed_varints(buf: memoryview):
    i, n = 0, len(buf)
    while i < n:
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        yield 38, 0, out


def module_scopes(xspace: bytes
                  ) -> Dict[str, Dict[str, Tuple[str, Optional[str]]]]:
    """{module name as the trace shows it, e.g. ``jit_f(5)``: {instruction
    name: (opcode, scope)}} from the HLO protos of the metadata plane (XSpace
    planes=1; plane name=2, event_metadata=4 (map: key=1, value=2);
    event metadata name=2, stats=5; stat bytes_value=6)."""
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for num, _, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and _text(v) == METADATA_PLANE
                   for n, _, v in fields):
            continue
        for n, _, entry in fields:
            if n != 4:
                continue
            for en, _, em in _fields(entry):
                if en != 2:
                    continue
                name, scopes = "", {}
                for mn, _, mv in _fields(em):
                    if mn == 2:
                        name = _text(mv)
                    elif mn == 5:
                        for sn, _, sv in _fields(mv):
                            if sn == 6:
                                scopes.update(_instruction_scopes(sv))
                out[name] = scopes
    return out


# ----------------------------------------------------------- reduction
@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over device planes
    devices: int
    op_s: Dict[str, float]              # device seconds per op
    scope_s: Dict[str, float]           # device seconds per kernel scope
    gaps: List[Tuple[str, float]]       # (host annotation, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _op_label(module: str, text: str) -> Tuple[str, str]:
    """(instruction name, short label) of an ``XLA Ops`` event, whose
    name is the instruction's HLO text (``%fusion.3 = f32[32,512]{...}
    fusion(...)``)."""
    head = text.split(" = ", 1)
    instr = head[0].lstrip("%")
    result = head[1].split("{", 1)[0].split("(", 1)[0] if len(head) > 1 \
        else ""
    mod = module.split("(", 1)[0]
    return instr, f"{mod}/{instr} {result}".strip()


# ops whose events enclose the events of the ops they run
CONTAINERS = frozenset({"while", "conditional", "call"})


def reduce(profile, scopes) -> Optional[Reduction]:
    """The window's device metrics, or None where the trace holds no
    window or no device plane. ``scopes`` is :func:`module_scopes`; a
    control-flow op (while, conditional, call) is not counted itself,
    since the ops it runs are."""
    window = None
    host: List[Tuple[int, int, str]] = []
    planes = list(profile.planes)
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith("bench."):
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if window is None or not devices:
        return None
    w0, w1 = window
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    scope_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        starts = [m[0] for m in mods]
        spans = []
        for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if b <= a:
                continue
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            module = mods[j][2] if j >= 0 and ev.start_ns < mods[j][1] \
                else ""
            instr, label = _op_label(module, ev.name)
            opcode, scope = scopes.get(module, {}).get(instr, ("", None))
            if opcode in CONTAINERS:
                continue
            spans.append((a, b))
            s = (b - a) * 1e-9
            op_s[label] = op_s.get(label, 0.0) + s
            if scope is not None:
                scope_s[scope] = scope_s.get(scope, 0.0) + s
        busy = _union(spans)
        busy_total += sum(b - a for a, b in busy) * 1e-9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_annotation(host, a, b), (b - a) * 1e-9))
    n = len(devices)
    return Reduction(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n,
                     devices=n,
                     op_s={k: v / n for k, v in op_s.items()},
                     scope_s={k: v / n for k, v in scope_s.items()},
                     gaps=gaps)


def _annotation(host, a: int, b: int) -> str:
    """The shortest benchmark annotation covering the middle of [a, b]
    (the innermost one), or ``bench.front`` where none does: time the
    host spent outside every engine call, in the front or the caller."""
    mid = (a + b) // 2
    best = None
    for s, e, name in host:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "bench.front"


def reduce_bytes(raw: bytes) -> Optional[Reduction]:
    """:func:`reduce` of a serialized ``XSpace``."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_serialized_xspace(raw),
                  module_scopes(raw))


def reduce_file(path: str) -> Optional[Reduction]:
    """:func:`reduce` of an ``.xplane.pb`` file, or of its gzip copy
    (``.xplane.pb.gz``)."""
    import gzip

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce_bytes(f.read())


def reduce_dir(log_dir: str) -> Optional[Reduction]:
    path = find_xplane(log_dir)
    return None if path is None else reduce_file(path)
