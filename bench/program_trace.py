"""What the serving program names in a profiler trace: its engine spans and
the layer scope of every device operation.

``trace.reduce`` reads the device's operations and programs and the load
generator's spans.  On the same clock the program also records:

* host spans named ``engine.*`` (``serving/engine.py``; ``docs/SERVING.md``,
  "Tracing"), each with the arguments the engine gave it, such as
  ``engine.generate``'s ``rows``, ``slots``, ``fill`` and ``capacity``;
* the scope path of every device operation (``jax.named_scope``:
  ``qdense/<site>``, ``kv_write``, ``attention``, ``sample``,
  ``guardrail``, ``moe_dispatch``): its HLO instruction's ``op_name``.
  A v5e trace's operation events carry no ``op_name`` (their stats are
  offsets and durations alone), so it is read from the optimized HLO of
  the programs that ran (``program_texts``), matched by module, by
  instruction name and by result type.

``extend`` returns ``trace.reduce``'s reduction with both added
(``Reduced.program``, ``Event.scope``); every function of ``trace`` and
every metric reader reads it as before.  The functions below read it:
device time by scope, idle time by the engine span open across it, and
the engine's own counts.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

runs the cell once as ``bench/run.py --trace 1`` does, printing its result
line, then one more JSON line with those readings.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace as T  # noqa: E402

# scopes the program opens around one layer's device work; ``qdense`` is
# followed by the dense site's leaf name (``qdense/wq``)
SCOPES = ("kv_write", "attention", "sample", "guardrail", "moe_dispatch")
UNSCOPED = "unscoped"
OUTSIDE = "outside_engine"
INSERT_PROGRAM = "_insert_fn"


@dataclasses.dataclass
class Event(T.Event):
    scope: str = ""  # the operation's op_name path, "" where it has none


@dataclasses.dataclass
class Span(T.Event):
    args: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reduced(T.Reduced):
    program: List[Span] = dataclasses.field(default_factory=list)


def layer(path: str) -> str:
    """The innermost layer scope on an op_name path: ``qdense/<site>``,
    one of ``SCOPES``, or ``unscoped``.  A fusion's op_name is its root
    instruction's, so a fusion that mixes scopes counts for its root's."""
    parts = path.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] == "qdense" and i + 1 < len(parts):
            return f"qdense/{parts[i + 1]}"
        if parts[i] in SCOPES:
            return parts[i]
    return UNSCOPED


_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _instruction(text: str) -> Optional[Tuple[str, str, str]]:
    """(name, result type, rest) of one HLO instruction's text."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    name, rest = m.groups()
    if rest.startswith("("):  # a tuple: up to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return name, rest[:i + 1], rest
    return name, rest.split(" ", 1)[0], rest


def hlo_paths(text: str) -> Tuple[str, Dict[str, Tuple[str, str]]]:
    """The module name of an optimized HLO module's text, and each of its
    instructions' result type and ``op_name``."""
    module = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
    out = {}
    for line in text.splitlines():
        ins = _instruction(line)
        if ins is not None:
            op = _OP_NAME.search(ins[2])
            out[ins[0]] = (ins[1], op.group(1) if op else "")
    return module, out


def _scope_paths(red: T.Reduced, texts: Sequence[str]) -> Dict[int, str]:
    """op_name path of each operation (by ``id``) that runs inside a
    program of ``texts``.  A program compiled at several shapes (a prefill
    chunk of each size) has one text per shape; each execution in the
    trace (a module event, named ``<module>(<fingerprint>)``) is matched to
    the text whose instructions its operations agree with most."""
    variants: Dict[str, list] = {}
    for text in texts:
        module, instrs = hlo_paths(text)
        variants.setdefault(module, []).append(instrs)
    parsed: Dict[str, Optional[Tuple[str, str, str]]] = {}
    chosen: Dict[str, dict] = {}
    paths: Dict[int, str] = {}
    for m, inside in zip(red.modules, T.module_ops(red)):
        cands = variants.get(m.name.split("(", 1)[0])
        if not cands:
            continue
        keys = []
        for o in inside:
            if o.name not in parsed:
                parsed[o.name] = _instruction(o.name)
            keys.append(parsed[o.name])
        if m.name not in chosen:
            chosen[m.name] = max(cands, key=lambda v: sum(
                1 for k in keys if k and v.get(k[0], ("",))[0] == k[1]))
        instrs = chosen[m.name]
        for o, k in zip(inside, keys):
            if k and instrs.get(k[0], ("",))[0] == k[1]:
                paths[id(o)] = instrs[k[0]][1]
    return paths


def extend(red: T.Reduced, planes: Iterable,
           texts: Sequence[str] = ()) -> Reduced:
    """``red`` with the engine's host spans, read from the trace's planes
    (``ProfileData.planes``), and each operation's scope path, read from
    the optimized HLO ``texts`` of the programs that ran."""
    paths = _scope_paths(red, texts)
    ops = [Event(o.name, o.start, o.end, o.kind, paths.get(id(o), ""))
           for o in red.ops]
    program = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    program.append(Span(ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        args=dict(ev.stats)))
    program.sort(key=lambda s: s.start)
    return Reduced(ops, red.modules, red.spans, program)


def program_texts(eng) -> List[str]:
    """The optimized HLO text of a ``StagedEngine``'s decode tick, of its
    prefill program at each chunk size the scheduler cuts (the chunk and
    every smaller power of two, ``scheduler.chunk_plan``) and of its insert,
    lowered as the engine dispatches them, so that JAX hands back the
    executables that ran from its in-memory cache.  The persistent cache is
    off meanwhile: a program not held in memory is compiled again, with its
    instructions' metadata, rather than loaded."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    chunk = eng.sched.prefill_chunk
    sizes = [chunk] + [1 << i for i in range(chunk.bit_length())
                       if 1 << i < chunk]
    prefix = eng.api.init_cache(1, eng.max_len)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        texts = [eng.compiled_programs()["decode"].as_text()]
        with eng._dispatch():
            for n in sizes:
                texts.append(eng._prefill_step.lower(
                    eng.params, jnp.zeros((1, n), jnp.int32), jnp.int32(0),
                    prefix).compile().as_text())
            texts.append(eng._insert_step.lower(
                eng.cache, prefix, jnp.int32(0)).compile().as_text())
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return texts


def _in_window(red: T.Reduced, xs):
    a, b = T.window(red)
    return [x for x in xs if a <= x.start < b]


def spans(red: Reduced, name: str) -> List[Span]:
    """The program's spans called ``name`` that start in the window."""
    return _in_window(red, [s for s in red.program if s.name == name])


def scope_seconds(red: Reduced) -> Dict[str, float]:
    """Device seconds in the window by layer scope (``layer``), longest
    first; control-flow parents are left out, as in ``breakdown``."""
    a, b = T.window(red)
    tally: Dict[str, float] = {}
    for o in red.ops:
        if o.end <= a or o.start >= b or o.kind == T.PARENT:
            continue
        key = layer(o.scope)
        tally[key] = tally.get(key, 0.0) + o.dur * 1e-9
    return dict(sorted(tally.items(), key=lambda kv: -kv[1]))


def _innermost(program: List[Span]) -> List[Tuple[float, float, str]]:
    """Disjoint pieces of the timeline, each named by the innermost engine
    span open across it (spans of one thread nest)."""
    pts = sorted({p for s in program for p in (s.start, s.end)})
    order = sorted(program, key=lambda s: (s.start, -s.end))
    out, stack, i = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while i < len(order) and order[i].start <= a:
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end <= a:
            stack.pop()
        if stack:
            out.append((a, b, stack[-1].name))
    return out


def idle_by_span(red: Reduced) -> Dict[str, float]:
    """The device's idle seconds in the window, by the innermost engine
    span open across each idle moment (``outside_engine`` where none is
    open), longest first."""
    a, b = T.window(red)
    busy = T.busy_intervals(red)
    edges = [a] + [x for iv in busy for x in iv] + [b]
    idle = [(x, y) for x, y in zip(edges[0::2], edges[1::2]) if y > x]
    out: Dict[str, float] = {}
    pieces, j = _innermost(red.program), 0
    for x, y in idle:
        named = 0.0
        while j < len(pieces) and pieces[j][1] <= x:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < y:
            s, e, name = pieces[k]
            d = min(e, y) - max(s, x)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
                named += d
            k += 1
        if y - x > named:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (y - x - named) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# -- readings ------------------------------------------------------------
def engine_host_ms(red: Reduced) -> Optional[float]:
    """Mean over ``engine.step`` spans of their length less their
    ``engine.sync`` children, in ms."""
    steps = spans(red, "engine.step")
    if not steps:
        return None
    syncs = [s for s in red.program if s.name == "engine.sync"]
    host = [st.dur - sum(s.dur for s in syncs
                         if s.start >= st.start and s.end <= st.end)
            for st in steps]
    return 1e-6 * sum(host) / len(host)


def _share(red: Reduced, names, num: str, den: str) -> Optional[float]:
    xs = [s for n in names for s in spans(red, n)]
    total = sum(s.args.get(den, 0) for s in xs)
    return 100.0 * sum(s.args.get(num, 0) for s in xs) / total if total else None


def decode_rows_share(red: Reduced) -> Optional[float]:
    """Rows served over slots, summed over ``engine.generate``, in %."""
    return _share(red, ("engine.generate",), "rows", "slots")


def kv_use_share(red: Reduced, names=("engine.generate",)) -> Optional[float]:
    """Cache tokens held over cache tokens allocated (``fill`` over
    ``capacity``), summed over the ``names`` spans, in %."""
    return _share(red, names, "fill", "capacity")


def kv_write_ms(red: Reduced) -> Optional[float]:
    """Device ms per decode-tick program of its operations scoped
    ``kv_write``; None where no operation carries a scope path."""
    if not any(getattr(o, "scope", "") for o in red.ops):
        return None
    a, b = T.window(red)
    ticks = [(m, inside) for (m, kind), inside in
             zip(T.classify_modules(red), T.module_ops(red))
             if kind == "generate" and a <= m.start < b]
    if not ticks:
        return None
    ns = sum(o.dur for _, inside in ticks for o in inside
             if o.kind != T.PARENT and layer(getattr(o, "scope", "")) == "kv_write")
    return 1e-6 * ns / len(ticks)


def insert_ms(red: T.Reduced) -> Optional[float]:
    """Mean device ms of the engine's insert programs (``_insert_fn``) in
    the window; None where the program has no program of that name."""
    xs = _in_window(red, [m for m in red.modules if INSERT_PROGRAM in m.name])
    return 1e3 * sum(m.dur * 1e-9 for m in xs) / len(xs) if xs else None


def readings(red: Reduced) -> dict:
    busy = sum(y - x for x, y in T.busy_intervals(red)) * 1e-9
    by_scope = scope_seconds(red)
    return {
        "busy_s": busy,
        "scoped_s": sum(by_scope.values()),
        "device_s_by_scope": by_scope,
        "idle_s_by_span": idle_by_span(red),
        "spans": {n: len(spans(red, n)) for n in sorted(
            {s.name for s in red.program})},
        "engine_host_ms": engine_host_ms(red),
        "decode_rows_share": decode_rows_share(red),
        "kv_use_share.generate": kv_use_share(red),
        "kv_use_share.generate_prefill": kv_use_share(
            red, ("engine.generate", "engine.prefill")),
        "kv_write_ms": kv_write_ms(red),
        "insert_ms": insert_ms(red),
    }


def main(argv=None) -> int:
    """One traced run of a cell through ``bench/run.py``, whose trace is
    reduced here with the program's spans and scopes as well."""
    import json

    from bench import run

    kept, engines = [], []
    plain, build = T.reduce, run.build_engine

    def build_and_keep(*args):
        engines.append(build(*args))
        return engines[-1]

    def reduce_and_keep(path):
        # the engine is still alive here: compile its programs' texts, and
        # hold it no longer than that (the run frees it for the reference)
        from jax.profiler import ProfileData

        texts = program_texts(engines.pop())
        red = extend(plain(path), ProfileData.from_file(path).planes, texts)
        kept.append(red)
        return red

    T.reduce, run.build_engine = reduce_and_keep, build_and_keep
    try:
        rc = run.main(list(sys.argv[1:] if argv is None else argv)
                      + ["--trace", "1"])
    finally:
        T.reduce, run.build_engine = plain, build
    if rc == 0 and kept:
        print(json.dumps(readings(kept[0])))
    return rc


if __name__ == "__main__":
    sys.exit(main())
