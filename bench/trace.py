"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Planes named ``/device:TPU:<n>`` hold the device; their ``XLA Ops`` line has
one event per operation, named by its HLO text (``%name = shape op(...)``).
The host plane ``/host:CPU`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans on the same clock.  Everything here
is pure Python over (start, end) pairs in nanoseconds, so it is tested on a
small recorded trace without a chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: host spans the benchmark opens around its calls into the program
HOST_SPANS = ("bench.submit", "bench.wait", "engine.step_begin",
              "engine.step_finish", "router.step")
UNTRACED = "untraced"

_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_SHAPE = re.compile(r"(bf16|f32|f16|s32|s8|u32|f8e4m3fn|s4)\[([\d,]*)\]")


@dataclass
class Op:
    name: str  # HLO text
    start: float  # ns
    end: float


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    modules: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Op(e.name, e.start_ns, e.end_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    mods.extend(Op(e.name, e.start_ns, e.end_ns) for e in line.events)
            tr.devices[plane.name] = sorted(ops, key=lambda o: o.start)
            tr.modules[plane.name] = sorted(mods, key=lambda o: o.start)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        tr.spans.append((e.name, e.start_ns, e.end_ns))
    tr.spans.sort(key=lambda s: s[1])
    return tr


def clip(ivs: Iterable[Tuple[float, float]], lo: float, hi: float):
    for s, e in ivs:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def union(ivs: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(((o.start, o.end) for o in ops), lo, hi)))


def idle_gaps(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which no operation runs."""
    gaps, t = [], lo
    for s, e in union(clip(((o.start, o.end) for o in ops), lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(gaps: Sequence[Tuple[float, float]],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle nanoseconds by the innermost benchmark span open at the time;
    the part no span covers counts as ``untraced``."""
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        covered = []
        for name, s, e in spans:
            if e <= gs or s >= ge:
                continue
            covered.append((max(s, gs), min(e, ge), e - s, name))
        # innermost first: a shorter span nested in a longer one wins
        cuts = sorted({gs, ge, *[c[0] for c in covered], *[c[1] for c in covered]})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [c for c in covered if c[0] <= mid < c[1]]
            name = min(inside, key=lambda c: c[2])[3] if inside else UNTRACED
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def op_kind(hlo: str) -> str:
    """Stable name of an operation: its HLO instruction name without the
    numeric suffix (``%fusion.12 = ...`` → ``fusion``)."""
    m = _HLO_NAME.match(hlo)
    return m.group(1) if m else hlo.split(" ", 1)[0]


def operand_shapes(hlo: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, shape) of the result and then each operand, in HLO order."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(hlo.split(", custom_call_target")[0])]


def self_ns(ops: Sequence[Op]) -> List[float]:
    """Each op's duration less that of the ops nested in it (a ``while``
    event spans its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    own = [o.end - o.start for o in ops]
    stack: List[int] = []
    for i in order:
        while stack and ops[stack[-1]].end <= ops[i].start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i].end - ops[i].start
        stack.append(i)
    return own


def op_seconds(ops: Sequence[Op], lo: float, hi: float) -> Dict[str, float]:
    """Self time of each kind of op that starts inside [lo, hi]."""
    out: Dict[str, float] = {}
    for o, t in zip(ops, self_ns(ops)):
        if lo <= o.start < hi:
            k = op_kind(o.name)
            out[k] = out.get(k, 0.0) + t * 1e-9
    return out


def module_name(name: str) -> str:
    """``jit__decode(1234)`` → ``jit__decode``."""
    return name.split("(", 1)[0]


def module_seconds(mods: Sequence[Op], lo: float, hi: float) -> Dict[str, List[float]]:
    """[count, seconds] of each compiled program run inside [lo, hi]."""
    out: Dict[str, List[float]] = {}
    for o in mods:
        if lo <= o.start < hi:
            c = out.setdefault(module_name(o.name), [0, 0.0])
            c[0] += 1
            c[1] += (o.end - o.start) * 1e-9
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def summarize(tr: Trace, lo: float, hi: float) -> Dict:
    """Busy and idle seconds per device and in all, the top device ops and
    idle time by host span, over the window [lo, hi] (ns)."""
    window = (hi - lo) * 1e-9
    busy, ops_s, idle = [], {}, {}
    for ops in tr.devices.values():
        busy.append(busy_ns(ops, lo, hi) * 1e-9)
        for k, v in op_seconds(ops, lo, hi).items():
            ops_s[k] = ops_s.get(k, 0.0) + v
        for k, v in attribute(idle_gaps(ops, lo, hi), tr.spans).items():
            idle[k] = idle.get(k, 0.0) + v * 1e-9
    mods: Dict[str, List[float]] = {}
    for ms in tr.modules.values():
        for k, (c, t) in module_seconds(ms, lo, hi).items():
            m = mods.setdefault(k, [0, 0.0])
            m[0] += c
            m[1] += t
    n = max(len(busy), 1)
    return {
        "modules": sorted(([k, c, t] for k, (c, t) in mods.items()), key=lambda x: -x[2]),
        "window_s": window,
        "busy_s": sum(busy) / n,
        "device_ops": top({k: v / n for k, v in ops_s.items()}),
        "idle_gaps": top({k: v / n for k, v in idle.items()}),
    }


def kernel_events(tr: Trace, kernel: str, lo: float, hi: float) -> List[Op]:
    """Events of a Pallas kernel (HLO instruction named after it) inside
    the window, across devices."""
    out = []
    for ops in tr.devices.values():
        out.extend(o for o in ops if op_kind(o.name) == kernel and lo <= o.start < hi)
    return out


def window_of(tr: Trace) -> Optional[Tuple[float, float]]:
    """First and last instant the benchmark's spans cover."""
    if not tr.spans:
        return None
    return min(s for _, s, _ in tr.spans), max(e for _, _, e in tr.spans)
