"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``reduce`` returns the device's operation events (the ``XLA Ops`` line of
the first TPU plane), its program executions (``XLA Modules``), and the
load generator's host spans (``TraceAnnotation`` events), all on the trace's own
clock.  An operation event is named by its HLO instruction
(``%name = type op(...)``).  Control-flow parents (the layer scan's
``while``) hold other events inside them; they count toward busy time but
not toward any operation's own time.

The program names no kernel yet, so Pallas kernels are classed by the
names their custom calls carry today (a later change that names them
``fused_qmm*`` / ``flash_attend*`` still matches):
``<format>_matmul_fused`` is ``fused_qmm_call``, ``<format>_matmul`` its
unfused form, ``quantize_rows`` the activation quantizer, and
``closed_call`` -- the only other custom call the serving programs hold --
is ``flash_attend``.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Tuple

SPANS = ("wait_arrival", "submit", "step")
# kernel class -> patterns matched against a custom call's base name
KERNELS = (
    ("fused_qmm_call", r"_matmul_fused$|fused_qmm"),
    ("packed_qmm_call", r"_matmul$|packed_qmm"),
    ("quantize_rows", r"quantize"),
    ("flash_attend", r"^closed_call$|flash"),
)
PARENT = "parent"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float
    kind: str = ""  # kernel class, "" for other operations

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]


def base_name(name: str) -> str:
    """``fusion.12`` of ``%fusion.12 = f32[8] fusion(...)``, without its
    numeric suffix: ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[._]\d+$", "", head)


def kernel_class(name: str) -> str:
    """The kernel an operation event runs, or "" for other operations."""
    if "custom-call(" not in name and " = " in name:
        return ""
    if "AllocateBuffer" in name:
        return ""
    base = base_name(name)
    for kind, pat in KERNELS:
        if re.search(pat, base):
            return kind
    return "other_kernel" if "custom-call(" in name else ""


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def _line(plane, want: str):
    for line in plane.lines:
        if line.name == want:
            return line
    return None


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not device:
        raise RuntimeError("the trace holds no TPU device plane: "
                           f"{[p.name for p in pd.planes]}")
    dev = sorted(device, key=lambda p: p.name)[0]
    ops, modules, spans = [], [], []
    line = _line(dev, "XLA Ops")
    if line is None:
        raise RuntimeError(f"no 'XLA Ops' line on {dev.name}: "
                           f"{[l.name for l in dev.lines]}")
    for ev in line.events:
        ops.append(Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         kernel_class(ev.name)))
    line = _line(dev, "XLA Modules")
    for ev in (line.events if line is not None else ()):
        modules.append(Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for hl in plane.lines:
            for ev in hl.events:
                if ev.name in SPANS:
                    spans.append(Event(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    for xs in (ops, modules, spans):
        xs.sort(key=lambda e: e.start)
    for op, nxt in zip(ops, ops[1:]):
        if nxt.start < op.end and nxt.end <= op.end:
            op.kind = PARENT  # it holds the next event inside it
    return Reduced(ops, modules, spans)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(busy: List[Tuple[float, float]], a: float, b: float) -> float:
    """Length of ``busy`` (disjoint, sorted) inside [a, b]."""
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in busy
               if y > a and x < b)


def module_ops(red: Reduced) -> List[List[Event]]:
    """The operation events inside each module event, by position."""
    out, j = [], 0
    for m in red.modules:
        while j < len(red.ops) and red.ops[j].start < m.start:
            j += 1
        k, inside = j, []
        while k < len(red.ops) and red.ops[k].start < m.end:
            inside.append(red.ops[k])
            k += 1
        out.append(inside)
    return out


def classify_modules(red: Reduced) -> List[Tuple[Event, str]]:
    """Each program execution as "generate" (the engine's decode tick),
    "prefill" (another program that runs dense-site kernels) or "other"
    (insert, first-token sampling, cache allocation)."""
    out = []
    for m, inside in zip(red.modules, module_ops(red)):
        if "tick_fn" in m.name:
            kind = "generate"
        elif any(o.kind in ("fused_qmm_call", "packed_qmm_call") for o in inside):
            kind = "prefill"
        else:
            kind = "other"
        out.append((m, kind))
    return out


def window(red: Reduced) -> Tuple[float, float]:
    """From the first load-generator span's start to the later of the last span's
    end and the last device operation's end."""
    if not red.spans:
        raise RuntimeError("the trace holds none of the load generator's spans")
    a = red.spans[0].start
    b = max(s.end for s in red.spans)
    if red.ops:
        b = max(b, red.ops[-1].end)
    return a, b


def busy_intervals(red: Reduced) -> List[Tuple[float, float]]:
    a, b = window(red)
    return [(max(x, a), min(y, b)) for x, y in
            union([(o.start, o.end) for o in red.ops]) if y > a and x < b]


def breakdown(red: Reduced, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, by kernel class or by
    operation name without its numeric suffix; and the longest idle gaps,
    each named by the load-generator span open across its middle."""
    a, b = window(red)
    tally: Dict[str, float] = {}
    for o in red.ops:
        if o.end <= a or o.start >= b or o.kind == PARENT:
            continue
        key = o.kind or base_name(o.name)
        tally[key] = tally.get(key, 0.0) + o.dur
    ops = sorted(tally.items(), key=lambda kv: -kv[1])[:top]
    busy = busy_intervals(red)
    edges = [a] + [x for iv in busy for x in iv] + [b]
    gaps = []
    for x, y in zip(edges[0::2], edges[1::2]):
        if y > x:
            mid = 0.5 * (x + y)
            label = next((s.name for s in red.spans if s.start <= mid < s.end),
                         "host")
            gaps.append((label, y - x))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps[:top]]}
