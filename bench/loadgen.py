"""Drive ``StagedEngine.step`` through one measured window.

Open loop: each request is submitted once it is due, and its latency is
timed from its due time, so a long step delays every request that became
due during it.  The arrivals due before the window are served first, so
the window opens on an engine in its steady state.  Closed loop: the
queue always holds the job's next request, so every slot is refilled as it
frees.  The load generator stamps each output token with its own clock
after the step that produced it, and wraps its calls in profiler spans
(``wait_arrival``, ``submit``, ``step``) that the trace reduction reads.
What each step dispatched is read afterwards from the engine's ``counts``
and from the requests' own progress: a decode tick serves one row for
every request that gained a token, and a prefill chunk is the next piece
of the one request in prefill, by the scheduler's ``chunk_plan``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.serving import Request
from repro.serving.scheduler import chunk_plan

clock = time.monotonic


@dataclasses.dataclass
class Record:
    spec: object  # traffic.Spec
    req: Request
    due_t: float  # due time (open loop) or submit time (closed loop)
    token_t: List[float] = dataclasses.field(default_factory=list)
    chunks: int = 0  # prefill chunks dispatched for it


@dataclasses.dataclass
class Dispatch:
    kind: str  # "prefill" | "generate"
    t0: float
    t1: float
    # generate: the cache fill level of every served row after its write;
    # prefill: the chunk's end, its first position and its length
    valid: List[int]
    start: int = 0
    size: int = 0


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Record]
    dispatches: List[Dispatch]
    drain_s: float  # seconds stepped after the window to reach first tokens


@jax.jit
def _barrier(x):
    return x + 1


class LoadGen:
    def __init__(self, eng):
        self.eng = eng
        self.live: Dict[int, Record] = {}
        self.records: List[Record] = []
        self.dispatches: List[Dispatch] = []
        self.sync()  # compiles the barrier in set-up

    @staticmethod
    def sync() -> None:
        """Wait until the device has run every program dispatched so far
        (a device runs its programs in the order they were dispatched)."""
        jax.block_until_ready(_barrier(jnp.int32(0)))

    def submit(self, spec, due_t: float) -> Record:
        req = Request(uid=spec.uid, prompt=spec.prompt,
                      max_new_tokens=spec.max_new)
        with jax.profiler.TraceAnnotation("submit"):
            self.eng.submit(req)
        if req.status != "queued":
            raise RuntimeError(f"request {spec.uid} not queued: {req.status} "
                               f"({req.reason})")
        rec = Record(spec, req, due_t)
        self.records.append(rec)
        self.live[spec.uid] = rec
        return rec

    def step(self) -> None:
        eng = self.eng
        before = dict(eng.counts)
        t0 = clock()
        with jax.profiler.TraceAnnotation("step"):
            eng.step()
        t1 = clock()
        # the one request in prefill: started, and no token before this step
        in_prefill = [r for r in self.live.values()
                      if r.req.prefill_start_t is not None and not r.token_t]
        gained = []
        for uid in list(self.live):
            rec = self.live[uid]
            new = len(rec.req.output) - len(rec.token_t)
            if new:
                rec.token_t.extend([t1] * new)
                gained.append(rec)
            if rec.req.terminal:
                del self.live[uid]
        if eng.counts["generate_ticks"] != before["generate_ticks"]:
            valid = [len(r.req.prompt) + len(r.req.output) - 1 for r in gained]
            self.dispatches.append(Dispatch("generate", t0, t1, valid))
        elif eng.counts["prefill_chunks"] != before["prefill_chunks"]:
            if len(in_prefill) != 1:
                raise RuntimeError(f"{len(in_prefill)} requests in prefill")
            rec = in_prefill[0]
            plan = chunk_plan(len(rec.req.prompt), eng.sched.prefill_chunk)
            start, size = sum(plan[:rec.chunks]), plan[rec.chunks]
            rec.chunks += 1
            self.dispatches.append(
                Dispatch("prefill", t0, t1, [start + size], start, size))

    def _serve(self, specs, anchor: float, end: float, loop: str) -> None:
        """Serve until ``end``: open loop, ``specs`` fall due at ``anchor``
        plus their offsets; closed loop, the queue is refilled from them."""
        eng, i = self.eng, 0
        while True:
            now = clock()
            if loop == "open":
                while i < len(specs) and anchor + specs[i].due_s <= now:
                    self.submit(specs[i], anchor + specs[i].due_s)
                    i += 1
            if now >= end:
                return
            if loop == "closed":
                while not eng.queue and i < len(specs):
                    self.submit(specs[i], now)
                    i += 1
                if i == len(specs) and not eng.queue:
                    raise RuntimeError("the closed-loop job ran out of requests")
            if self.live:
                self.step()
            else:
                wake = end
                if loop == "open" and i < len(specs):
                    wake = min(anchor + specs[i].due_s, end)
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    time.sleep(max(0.0, wake - clock()))

    def run(self, specs, seconds: float, *, loop: str, on_open=None,
            on_close=None, drain_s: float = 60.0) -> Window:
        """Serve the arrivals due before the window (negative offsets) until
        it opens, then ``specs`` for ``seconds``; then, without new
        arrivals, step on (at most ``drain_s``) until every request due in
        the window has its first token.  ``on_open`` runs as the window
        opens and ``on_close`` once its device work has finished (the
        traced run starts and stops its trace there)."""
        early = [s for s in specs if s.due_s is not None and s.due_s < 0]
        if early:
            opens = clock() - early[0].due_s
            self._serve(early, opens, opens, loop)
            specs = specs[len(early):]
        if on_open is not None:
            on_open()
        t0 = clock()
        self._serve(specs, t0, t0 + seconds, loop)
        t1 = clock()
        self.sync()
        if on_close is not None:
            on_close()
        drain0 = clock()
        if loop == "open":
            while (any(not r.token_t for r in self.records)
                   and self.live and clock() < t1 + drain_s):
                self.step()
        return Window(t0, t1, self.records, self.dispatches,
                      clock() - drain0)
