"""Serving engines over slot-based decode state: the lockstep oracle and
the staged continuous-batching engine.

``ServingEngine`` (lockstep, Orca-style): all ``n_slots`` step through ONE
jitted decode graph per tick -- slots consuming their prompt feed the next
prompt token, generating slots feed their last sampled token, idle slots
feed a pad token whose output is discarded.  Simple, and bit-exact: it is
the token-parity oracle the staged engine is tested against.  Its weakness
is structural: prefill and decode share the tick, so a P-token prompt costs
P full-batch dispatches during which its slot emits nothing.

``StagedEngine`` splits the engine into three explicit stages
(JetStream/MaxEngine-style):

  * ``prefill`` -- a dedicated jitted graph consumes a whole prompt chunk
    (B=1, S=chunk) against a private cache, chunked at a configurable token
    budget so arbitrarily long prompts never monopolize a tick; families
    without a chunk graph (ssm/hybrid/encdec) fall back to budgeted
    per-token decode prefill into the same private cache.
  * ``insert`` -- a donated in-place write of the finished prefix into the
    decode cache's reserved slot (every leaf's batch row is overwritten, so
    stale state from the slot's previous occupant cannot leak).
  * ``generate`` -- the existing donated one-dispatch decode tick over the
    slot batch.

Admission is asynchronous: the scheduler (``repro.serving.scheduler``)
interleaves prefill chunks with generate ticks under a policy knob
(decode-priority vs prefill-priority) and tracks per-request queue-wait /
TTFT / TPOT, surfaced as p50/p95/p99 through ``stats()``.

Both engines share the slot bookkeeping, the donated device-resident tick
(one host sync per tick, transfer-guard-asserted), mesh installation, and
artifact cold start.  With identical seeds and prompts the two engines
produce bit-identical greedy tokens: chunked prefill writes exactly the
K/V rows the lockstep tick would have written, and attention masks stale
positions to exact zeros.  (Stochastic sampling consumes the PRNG stream
in dispatch order, which differs by construction; parity is a greedy
contract.  MoE capacity drops depend on which tokens share a dispatch, so
parity there additionally assumes drop-free capacity -- see
docs/SERVING.md.)

This engine layer is the system the paper's quantized weights serve from:
with PTQ params (QTensors) the decode step streams 2-bit/4-bit packed
weights -- the bandwidth-bound phase where cluster quantization pays off.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving import health as health_mod
from repro.serving.faults import FaultInjector
from repro.serving.health import HealthConfig, OverloadController, TickWatchdog
from repro.serving.sampler import SamplerConfig, sample
from repro.serving.scheduler import (
    AdmissionConfig,
    LatencyStats,
    PrefillTask,
    SchedulerConfig,
    admission_decision,
    chunk_plan,
    degraded_chunk,
    estimate_ttft_ms,
    next_action,
)

# terminal request statuses: the request has left the engine for good
TERMINAL_STATUSES = ("finished", "expired", "shed", "rejected", "failed",
                     "cancelled")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # fault-tolerance contract (caller-set):
    #   deadline_ms -- wall-clock budget from submit; past it the request is
    #       expired wherever it is (queued or in flight).  None = the
    #       engine's AdmissionConfig default (which may also be None).
    #   max_retries -- how many times a fault-quarantined request may be
    #       re-queued (exponential backoff) before it is failed for good.
    deadline_ms: Optional[float] = None
    max_retries: int = 0
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle: pending -> queued -> running -> finished, with the
    # fault-path terminals expired | shed | rejected | failed | cancelled
    status: str = "pending"
    reason: Optional[str] = None  # why shed/rejected/expired/failed/cancelled
    retries: int = 0  # quarantine retries consumed
    not_before: float = 0.0  # backoff gate: not re-admitted before this time
    admitted_tick: Optional[int] = None  # engine tick this request got a slot
    # wall-clock SLO trace (time.monotonic seconds), filled by the engine:
    # submit -> prefill_start (queue wait) -> first_token (TTFT) -> finish
    submit_t: Optional[float] = None
    prefill_start_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None

    @property
    def terminal(self) -> bool:
        return self.status in TERMINAL_STATUSES


class _EngineBase:
    """Slot/queue bookkeeping, device placement and the donated decode tick
    shared by the lockstep and staged engines."""

    def __init__(
        self,
        api,  # ModelApi
        params: Any,
        n_slots: int = 4,
        max_len: int = 256,
        sampler: SamplerConfig = SamplerConfig(),
        seed: int = 0,
        mesh: Optional[jax.sharding.Mesh] = None,
        admission: AdmissionConfig = AdmissionConfig(),
        health: HealthConfig = HealthConfig(),
        faults: Optional[FaultInjector] = None,
    ):
        from repro.parallel import sharding as rules

        self.api = api
        self.n_slots = n_slots
        self.max_len = max_len
        self.sampler = sampler
        self.mesh = mesh
        self.admission = admission
        self.health = health
        self.faults = faults
        self.watchdog = TickWatchdog(health)
        self._overload_ctl = OverloadController(health)
        # fault-tolerance event counters, surfaced via stats()["health"]
        self.events = {
            "rejected": 0, "shed": 0, "expired": 0, "cancelled": 0,
            "quarantined": 0, "retried": 0, "failed": 0,
            "faults_injected": 0,
        }
        self._tok_sharding = None
        self._pos_sharding = None
        self._cache_sharding = None
        # the activation mesh this engine's decode graph traces under: its
        # own mesh, or whatever was ambient at construction (a mesh-less
        # engine must not see another engine's mesh leak into its trace)
        self._trace_mesh = mesh if mesh is not None else rules._ACT_MESH[0]
        if mesh is not None:
            params = self._install_mesh(params)
        self.params = params
        if mesh is None:
            self.cache = api.init_cache(n_slots, max_len)
            self.key = jax.random.PRNGKey(seed)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            cache_shapes = jax.eval_shape(lambda: api.init_cache(n_slots, max_len))
            self._cache_sharding = rules.cache_shardings(cache_shapes, mesh)
            self.cache = jax.device_put(
                api.init_cache(n_slots, max_len), self._cache_sharding
            )
            self.key = jax.device_put(
                jax.random.PRNGKey(seed), NamedSharding(mesh, P())
            )

        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = np.zeros(n_slots, np.int32)  # next cache position
        self.slot_cursor = np.zeros(n_slots, np.int32)  # prompt consumption
        self.next_token = np.zeros(n_slots, np.int32)
        # deque: admission pops from the head every tick -- O(1) instead of
        # the O(n) list.pop(0) under deep backlogs
        self.queue: Deque[Request] = deque()
        self._tick = 0  # monotonically increasing engine tick counter
        self._clock = time.monotonic
        self._lat = LatencyStats()
        self._zero_prefix = None  # lazy B=1 zero cache (slot clearing)
        self._poison_prefix = None  # lazy B=1 NaN cache (chaos kv_corrupt)

        guardrails = health.guardrails
        sat_limit = float(2.0 ** health.sat_exponent)

        def _tick_fn(params, tokens, pos, cache, key, fault_slot, fault_val):
            logits, cache = api.decode(params, tokens, pos, cache)
            last = logits[:, -1, :].astype(jnp.float32)
            # chaos hook: overwrite ONE slot's logit row in-graph
            # (fault_slot == -1 selects nothing -- the fault-free path)
            rows = jnp.arange(last.shape[0], dtype=jnp.int32)[:, None]
            last = jnp.where(rows == fault_slot, fault_val, last)
            key, sub = jax.random.split(key)
            with jax.named_scope("sample"):
                toks = sample(sub, last, sampler)
            # numerical guardrail: ONE fused reduction over the tick's
            # logits -> per-slot poison bitflags, stacked with the sampled
            # tokens so flags ride the existing single host sync
            with jax.named_scope("guardrail"):
                if guardrails:
                    flags = health_mod.poison_flags(last, sat_limit)
                else:
                    flags = jnp.zeros_like(toks)
            return jnp.stack([toks, flags]), key, cache

        # donate the cache: the decode step's masked writes update it in
        # place instead of copying the whole (L, B, S, ...) buffer per tick
        self._decode_step = jax.jit(_tick_fn, donate_argnums=(3,))
        if api.insert is not None:
            jit_kw = {}
            if self._cache_sharding is not None:
                # pin the output layout so a donated sharded cache keeps the
                # serving sharding across insert dispatches
                jit_kw["out_shardings"] = self._cache_sharding

            def _insert_fn(cache, prefix, slot):
                return api.insert(cache, prefix, slot)

            self._insert_step = jax.jit(
                _insert_fn, donate_argnums=(0,), **jit_kw
            )
        else:
            self._insert_step = None

    def _install_mesh(self, params):
        """Install ``self.mesh`` as the serving layout: params onto the
        serving sharding rules, and the per-tick token/pos shardings (batch
        over data axes when divisible).  The ambient activation mesh is NOT
        mutated here -- each decode dispatch scopes it (``_dispatch``), so
        two engines with different meshes coexist in one process."""
        from repro.parallel import sharding as rules

        mesh = self.mesh
        params = jax.device_put(
            params, rules.qtensor_shardings(params, mesh, mode="serve")
        )
        # tokens (B, 1) / positions (B,) follow the one batch-sharding rule
        # (divisibility fallback included) instead of re-deriving it here
        specs = rules.batch_shardings(
            {
                "tokens": jax.ShapeDtypeStruct((self.n_slots, 1), jnp.int32),
                "pos": jax.ShapeDtypeStruct((self.n_slots,), jnp.int32),
            },
            mesh,
        )
        self._tok_sharding = specs["tokens"]
        self._pos_sharding = specs["pos"]
        return params

    @classmethod
    def from_artifact(cls, artifact_dir: str, **kwargs):
        """Cold-start an engine from a packed quantized artifact.

        The decode graph serves straight from the loaded QTensor tree under
        the artifact's compiled plan -- no fp32 weights, no calibration, no
        re-quantization on boot.  With ``mesh=...`` the artifact's payloads
        (including per-host ``payload.shard{k}`` files) assemble directly
        onto their owning devices."""
        from repro.models import load_servable  # lazy: serving stays model-agnostic

        api, qparams, _ = load_servable(artifact_dir, mesh=kwargs.get("mesh"))
        return cls(api, qparams, **kwargs)

    # -- client API --------------------------------------------------------
    def submit(self, req: Request, *, strict: bool = False) -> Request:
        """Admit, reject, or shed one request; returns it with ``status``
        set (``queued`` | ``rejected`` | ``shed``).

        Malformed requests (empty prompt, prompt that cannot fit
        ``max_len``) come back ``rejected`` with a reason instead of
        raising -- one bad client must not take the serve loop down.
        ``strict=True`` restores the old raise-on-malformed behavior for
        callers that want submission bugs loud.  Load shedding
        (``AdmissionConfig``: queue depth / estimated-TTFT SLO) returns
        ``shed`` in both modes -- overload is the server's fault, not a
        client bug, so it is never an exception.
        """
        req.submit_t = self._clock()
        reject = None
        if not req.prompt:
            reject = "empty prompt"
        elif len(req.prompt) >= self.max_len:
            reject = (
                f"prompt of {len(req.prompt)} tokens cannot fit engine "
                f"max_len={self.max_len}: the slot would hit the cache cap "
                "during prefill and finish with truncated or empty output; "
                "raise max_len or truncate the prompt"
            )
        if reject is not None:
            if strict:
                raise ValueError(reject)
            req.status, req.reason = "rejected", reject
            self.events["rejected"] += 1
            return req
        if req.deadline_ms is None:
            req.deadline_ms = self.admission.deadline_ms
        shed = admission_decision(
            self.admission,
            queue_depth=len(self.queue),
            est_ttft_ms=self._est_ttft_ms(),
            deadline_ms=req.deadline_ms,
        )
        if shed is not None:
            req.status, req.reason = "shed", shed
            self.events["shed"] += 1
            return req
        req.status = "queued"
        self.queue.append(req)
        return req

    def cancel(self, uid: int) -> bool:
        """Cancel request ``uid`` wherever it is -- queued or holding a
        slot (mid-prefill included).  Returns False if no live request with
        that uid is inside the engine."""
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[i]
                r.status, r.reason = "cancelled", "cancelled by client"
                self.events["cancelled"] += 1
                return True
        for s, r in enumerate(self.slot_req):
            if r is not None and r.uid == uid:
                self._abort_slot(s)
                r.status, r.reason = "cancelled", "cancelled by client"
                self.events["cancelled"] += 1
                return True
        return False

    def run(self, max_ticks: int = 1_000) -> List[Request]:
        """Step until idle or the tick budget expires; returns COMPLETED
        requests -- finished ones plus any that reached a terminal fault
        status (expired / failed) while running.  Check ``req.status``;
        without deadlines or faults every returned request is finished,
        exactly as before.  On budget expiry, in-flight and queued requests
        stay inside the engine -- inspect them with ``leftover()`` or pull
        them out with ``drain()``; they are never silently discarded."""
        completed: List[Request] = []
        ticks = 0
        while self._has_work() and ticks < max_ticks:
            tick0 = self._tick
            out = self.step()
            completed.extend(out)
            if self._tick == tick0 and not out and self.queue:
                # nothing dispatched and nothing completed: every queued
                # request is gated by retry backoff -- wait it out instead
                # of burning the tick budget on idle spins
                wait = min(r.not_before for r in self.queue) - self._clock()
                if wait > 0:
                    time.sleep(min(wait, 0.05))
            ticks += 1
        return completed

    def step(self) -> List[Request]:
        """One engine step: sweep deadlines, admit, choose and dispatch one
        stage/tick, feed the watchdog and overload controller.  Returns
        requests completed by this step (finished, expired, or failed).

        Profiler spans (``docs/SERVING.md``, "Tracing"): ``engine.step``
        around it all, ``engine.schedule`` around the sweep, admission and
        the choice; the dispatch adds its own."""
        with TraceAnnotation("engine.step"):
            t0 = self._clock()
            with TraceAnnotation("engine.schedule"):
                completed = self._expire_deadlines()
                action = self._schedule()
            tick0 = self._tick
            completed.extend(self._step_impl(action))
            if self._tick != tick0:  # a real dispatch happened: time it
                self.watchdog.observe(self._clock() - t0)
            self._overload_ctl.update(queue_depth=len(self.queue))
        return completed

    def leftover(self) -> Dict[str, List[Request]]:
        """Unfinished work still inside the engine, without removing it:
        ``in_flight`` (requests holding or reserving a slot, prompt possibly
        part-consumed, output possibly part-generated) and ``queued``
        (never admitted).  All have ``done=False`` -- callers distinguish
        starved requests from finished ones by this report, not by absence
        from ``run()``'s return."""
        in_flight = [r for r in self.slot_req if r is not None]
        return {"in_flight": in_flight, "queued": list(self.queue)}

    def drain(self) -> Dict[str, List[Request]]:
        """Remove and return all unfinished requests (``leftover()`` shape),
        resetting every slot.  After ``drain()`` the engine is empty and
        reusable."""
        report = self.leftover()
        self._abort_inflight()
        for s in range(self.n_slots):
            if self.slot_req[s] is not None:
                self._reset_slot(s)
        self.queue.clear()
        return report

    # -- slot lifecycle (the ONE place slot state is reset) ----------------
    def _reset_slot(self, s: int) -> None:
        """Return slot ``s`` to the idle state: no request, position 0, pad
        next-token.  Both completion and admission go through here, so a
        dead request's ``next_token``/``slot_cursor`` can never leak into
        the next occupant (or into the idle pad rows of the shared tick)."""
        self.slot_req[s] = None
        self.slot_pos[s] = 0
        self.slot_cursor[s] = 0
        self.next_token[s] = 0

    def _occupy_slot(self, s: int, req: Request) -> None:
        """Reserve slot ``s`` for ``req``: reset host state, clear the
        slot's device cache row (stale SSM/recurrent state is NOT masked by
        positions the way stale KV rows are), and stamp admission."""
        wait = 0.0 if req.submit_t is None else self._clock() - req.submit_t
        with TraceAnnotation("engine.admit", uid=req.uid, slot=s,
                             queue_wait_ms=wait * 1e3):
            self._reset_slot(s)
            self._clear_slot_cache(s)
            req.admitted_tick = self._tick
            req.prefill_start_t = self._clock()
            self.slot_req[s] = req

    def _clear_slot_cache(self, s: int) -> None:
        """Zero slot ``s``'s rows of the decode cache via the insert path.

        Stale KV rows are masked to exact zeros by the attention valid-mask,
        but recurrent state (ssm/hybrid families) carries the previous
        occupant unmasked -- clearing through the same ``insert`` write
        both engines use keeps slot reuse correct for every family."""
        if self._insert_step is None:
            return
        if self._zero_prefix is None:
            self._zero_prefix = self.api.init_cache(1, self.max_len)
        with self._dispatch():
            self.cache = self._insert_step(
                self.cache, self._zero_prefix, jnp.int32(s)
            )

    def _free_slot(self) -> Optional[int]:
        for s in range(self.n_slots):
            if self.slot_req[s] is None:
                return s
        return None

    def _finish(self, s: int, req: Request) -> None:
        req.done = True
        req.status = "finished"
        req.finish_t = self._clock()
        self._lat.record(req)
        if req.first_token_t is not None and len(req.output) > 1:
            self._overload_ctl.note_tpot_ms(
                (req.finish_t - req.first_token_t) / (len(req.output) - 1)
                * 1e3
            )
        self._reset_slot(s)

    def _abort_slot(self, s: int) -> None:
        """Tear one slot down mid-request (cancel / expiry / quarantine):
        host state reset AND device cache row scrubbed through the
        zero-prefix insert, so a poisoned or half-written row can never
        outlive its request."""
        self._reset_slot(s)
        self._clear_slot_cache(s)

    def _quarantine(self, s: int, req: Request, flag: int) -> Optional[Request]:
        """Contain a poisoned slot: abort it, scrub its cache, and either
        re-queue the request with exponential backoff (retry budget left)
        or fail it for good.  Returns the request when it terminated."""
        self.events["quarantined"] += 1
        self._abort_slot(s)
        reason = health_mod.describe_poison(flag)
        if req.retries < req.max_retries:
            req.retries += 1
            self.events["retried"] += 1
            backoff_s = (
                self.admission.retry_backoff_ms
                * (2 ** (req.retries - 1)) / 1e3
            )
            req.not_before = self._clock() + backoff_s
            # restart from the prompt: partial output came from (or fed
            # into) a poisoned cache and cannot be trusted
            req.output.clear()
            req.first_token_t = None
            req.status, req.reason = "queued", f"retrying after {reason}"
            self.queue.append(req)
            return None
        req.status = "failed"
        req.reason = f"{reason} (retry budget exhausted)" if req.max_retries \
            else reason
        req.finish_t = self._clock()
        self.events["failed"] += 1
        return req

    # -- deadlines / admission ---------------------------------------------
    def _deadline_passed(self, req: Request, now: float) -> bool:
        return (
            req.deadline_ms is not None
            and req.submit_t is not None
            and (now - req.submit_t) * 1e3 > req.deadline_ms
        )

    def _expire_deadlines(self) -> List[Request]:
        """Expire queued and in-flight requests past their deadline; frees
        their slots so live requests take them.  Returns the expired."""
        now = self._clock()
        expired: List[Request] = []
        if any(self._deadline_passed(r, now) for r in self.queue):
            keep: Deque[Request] = deque()
            for r in self.queue:
                if self._deadline_passed(r, now):
                    expired.append(r)
                else:
                    keep.append(r)
            self.queue = keep
        for s, r in enumerate(self.slot_req):
            if r is not None and self._deadline_passed(r, now):
                self._abort_slot(s)
                expired.append(r)
        for r in expired:
            r.status = "expired"
            r.reason = f"deadline {r.deadline_ms:.0f}ms exceeded"
            r.finish_t = now
            self.events["expired"] += 1
        return expired

    def _pop_eligible(self) -> Optional[Request]:
        """Oldest queued request not gated by retry backoff (FIFO among the
        eligible)."""
        now = self._clock()
        for i, r in enumerate(self.queue):
            if r.not_before <= now:
                del self.queue[i]
                return r
        return None

    def _est_ttft_ms(self) -> float:
        return estimate_ttft_ms(
            queued_tokens=sum(len(r.prompt) for r in self.queue),
            n_queued=len(self.queue),
            tick_ms=self.watchdog.ewma_ms,
            chunk=self._prefill_chunk_hint(),
        )

    def _prefill_chunk_hint(self) -> Optional[int]:
        """Tokens one dispatch consumes during prefill (None = one per
        tick, the lockstep model); the staged engine overrides."""
        return None

    # -- chaos -------------------------------------------------------------
    def _draw_fault(self):
        """Consume one injector decision for this dispatch.  Logit faults
        return in-graph operands (slot, value); cache/stall faults are
        applied here.  Fault-free: (-1, 0.0) -- the graph's no-op path."""
        no_fault = (jnp.int32(-1), jnp.float32(0.0))
        if self.faults is None:
            return no_fault
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        ev = self.faults.draw(self._tick, active)
        if ev is None:
            return no_fault
        self.events["faults_injected"] += 1
        victim = self.slot_req[ev.slot] if 0 <= ev.slot < self.n_slots \
            else None
        ev.uid = victim.uid if victim is not None else None
        if ev.kind in ("nan_logits", "inf_logits", "sat_logits"):
            return jnp.int32(ev.slot), jnp.float32(ev.payload)
        if ev.kind == "kv_corrupt":
            self._corrupt_slot_cache(ev.slot)
        elif ev.kind == "stall_tick":
            time.sleep(float(ev.payload))
        return no_fault

    def _corrupt_slot_cache(self, s: int) -> None:
        """Chaos: NaN-fill every float leaf of slot ``s``'s decode-cache
        row via the same donated insert the engine scrubs with."""
        if self._insert_step is None:
            return
        if self._poison_prefix is None:
            zero = self.api.init_cache(1, self.max_len)
            self._poison_prefix = jax.tree.map(
                lambda x: jnp.full_like(x, jnp.nan)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                zero,
            )
        with self._dispatch():
            self.cache = self._insert_step(
                self.cache, self._poison_prefix, jnp.int32(s)
            )

    def _check_done(self, s: int, tok: int, req: Request) -> bool:
        hit_eos = req.eos_id is not None and tok == req.eos_id
        return (
            len(req.output) >= req.max_new_tokens
            or hit_eos
            or self.slot_pos[s] >= self.max_len - 1
        )

    # -- device plumbing ---------------------------------------------------
    def _device_operands(self):
        tokens = self.next_token[:, None]
        pos = self.slot_pos
        if self.mesh is None:
            return jnp.asarray(tokens), jnp.asarray(pos)
        return (
            jax.device_put(tokens, self._tok_sharding),
            jax.device_put(pos, self._pos_sharding),
        )

    @contextlib.contextmanager
    def _dispatch(self):
        """Scope one device dispatch: the ambient activation mesh is set to
        this engine's trace mesh (MoE dispatch constraints + the shard_map
        EP path read it at trace time) and always restored, so engines
        never leak their mesh into each other; the transfer guard turns
        "no host sync inside a dispatch" from a convention into a runtime
        assert -- any device->host readback (stray float(), logits fetch,
        ...) raises."""
        from repro.parallel import sharding as rules

        prev_mesh = rules._ACT_MESH[0]
        rules.set_activation_mesh(self._trace_mesh)
        try:
            with jax.transfer_guard_device_to_host("disallow"):
                yield
        finally:
            rules.set_activation_mesh(prev_mesh)

    # -- hooks -------------------------------------------------------------
    def _has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def _abort_inflight(self) -> None:
        """Engine-specific teardown of partially-prefilled state (drain)."""

    def _schedule(self) -> str:  # pragma: no cover - abstract
        """Admit what the engine can and choose this step's dispatch:
        ``"prefill"``, ``"generate"`` or ``"idle"``."""
        raise NotImplementedError

    def _step_impl(self, action: str) -> List[Request]:  # pragma: no cover
        raise NotImplementedError

    # -- introspection ------------------------------------------------------
    def compiled_programs(self) -> Dict[str, Any]:
        """The engine's own jitted programs, compiled at the shapes it
        dispatches (the decode tick over every slot), to inspect what runs
        on the device."""
        tokens, pos = self._device_operands()
        with self._dispatch():
            return {"decode": self._decode_step.lower(
                self.params, tokens, pos, self.cache, self.key,
                jnp.int32(-1), jnp.float32(0.0),
            ).compile()}

    def stats(self) -> Dict[str, Any]:
        return {
            "active": sum(r is not None for r in self.slot_req),
            "queued": len(self.queue),  # queue depth (requests awaiting a slot)
            "tick": self._tick,
            "admitted_tick": [
                r.admitted_tick if r is not None else None
                for r in self.slot_req
            ],
            "positions": self.slot_pos.tolist(),
            "mesh": None if self.mesh is None else dict(self.mesh.shape),
            # per-request SLO percentiles over FINISHED requests (seconds):
            # queue_wait (submit -> slot), ttft (submit -> first token),
            # tpot (per output token after the first); None until recorded
            "latency": self._lat.summary(),
            # fault-tolerance: watchdog tick timing, overload mode, and the
            # shed/expired/quarantine/retry event counters
            "health": {
                **self.watchdog.summary(),
                **self._overload_ctl.summary(),
                "events": dict(self.events),
                "faults": None if self.faults is None
                else self.faults.summary(),
            },
        }

    @property
    def overload(self) -> bool:
        """Is the engine currently in degraded (overload) mode?"""
        return self._overload_ctl.overload


class ServingEngine(_EngineBase):
    """Lockstep tick loop (admission between ticks, prefill and decode in
    one shared graph).  Kept as the bit-exact oracle for ``StagedEngine``
    and as the simplest correct engine."""

    # -- engine tick -------------------------------------------------------
    def _admit(self) -> None:
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self.queue:
                req = self._pop_eligible()
                if req is None:  # whole queue gated by retry backoff
                    return
                self._occupy_slot(s, req)
                self.slot_cursor[s] = 1  # token 0 goes in this tick
                self.next_token[s] = req.prompt[0]

    def _schedule(self) -> str:
        self._admit()
        return "generate" if any(r is not None for r in self.slot_req) \
            else "idle"

    def _step_impl(self, action: str) -> List[Request]:
        """One lockstep tick over all slots; returns requests completed."""
        if action == "idle":
            return []
        self._tick += 1
        fault_slot, fault_val = self._draw_fault()
        tokens, pos = self._device_operands()
        with self._dispatch():
            out, self.key, self.cache = self._decode_step(
                self.params, tokens, pos, self.cache, self.key,
                fault_slot, fault_val,
            )
        out = np.asarray(out)  # the ONE host sync per tick
        sampled, flags = out[0], out[1]

        completed: List[Request] = []
        for s, req in enumerate(self.slot_req):
            if req is None:
                continue
            if flags[s]:  # guardrail tripped: contain before consuming
                dead = self._quarantine(s, req, int(flags[s]))
                if dead is not None:
                    completed.append(dead)
                continue
            self.slot_pos[s] += 1
            if self.slot_cursor[s] < len(req.prompt):  # still prefilling
                self.next_token[s] = req.prompt[self.slot_cursor[s]]
                self.slot_cursor[s] += 1
                continue
            tok = int(sampled[s])
            if not req.output:
                req.first_token_t = self._clock()
            req.output.append(tok)
            if self._check_done(s, tok, req):
                completed.append(req)
                self._finish(s, req)
            else:
                self.next_token[s] = tok
        return completed


class StagedEngine(_EngineBase):
    """Staged continuous batching: prefill / insert / generate stages with
    asynchronous admission, chunked prefill and per-request SLO stats.

    Each ``step()`` dispatches exactly ONE stage -- a prefill chunk or a
    generate tick -- chosen by the scheduler policy, so a long prompt costs
    its running co-residents at most one chunk of extra latency between
    ticks instead of stalling the batch for the whole prompt."""

    def __init__(
        self,
        api,
        params: Any,
        *,
        sched: SchedulerConfig = SchedulerConfig(),
        **kwargs,
    ):
        super().__init__(api, params, **kwargs)
        if self.api.insert is None:
            raise ValueError(
                f"model family {api.cfg.family!r} exposes no per-slot cache "
                "insertion (ModelApi.insert); the staged engine cannot move "
                "a finished prefill into the decode cache"
            )
        if sched.prefill_chunk >= self.max_len:
            sched = dataclasses.replace(sched, prefill_chunk=self.max_len - 1)
        self.sched = sched
        self._pf: Optional[PrefillTask] = None
        self._last_action = "generate"
        self.counts = {"prefill_chunks": 0, "generate_ticks": 0, "inserts": 0}
        if api.prefill_chunk is not None:
            def _prefill_fn(params, tokens, start, cache):
                return api.prefill_chunk(params, tokens, start, cache)

            self._prefill_step = jax.jit(_prefill_fn, donate_argnums=(3,))
        else:
            # fallback chunked prefill: budgeted per-token decode into the
            # private B=1 cache (recurrent families have no chunk graph)
            def _pf_decode_fn(params, tokens, pos, cache):
                return api.decode(params, tokens, pos, cache)

            self._prefill_step = None
            self._pf_decode = jax.jit(_pf_decode_fn, donate_argnums=(3,))

        guardrails = self.health.guardrails
        sat_limit = float(2.0 ** self.health.sat_exponent)

        def _first_token(key, logits):
            key, sub = jax.random.split(key)
            last = logits[:, -1, :].astype(jnp.float32)
            with jax.named_scope("sample"):
                toks = sample(sub, last, self.sampler)
            # same fused guardrail as the decode tick: a poisoned prefill
            # must be caught before its first token is served
            with jax.named_scope("guardrail"):
                if guardrails:
                    flags = health_mod.poison_flags(last, sat_limit)
                else:
                    flags = jnp.zeros_like(toks)
            return jnp.stack([toks, flags]), key

        self._first_token = jax.jit(_first_token)

    # -- scheduling --------------------------------------------------------
    def _decode_ready(self) -> bool:
        """Any slot actively generating (occupied and not merely reserved
        by the in-flight prefill)?"""
        reserved = self._pf.slot if self._pf is not None else None
        return any(
            r is not None and s != reserved for s, r in enumerate(self.slot_req)
        )

    def _effective_chunk(self) -> int:
        """Prefill chunk budget for NEW tasks: the configured chunk, or the
        degraded power-of-two half under overload (already in the compiled
        remainder-shape set, so degradation never compiles)."""
        chunk = self.sched.prefill_chunk
        return degraded_chunk(chunk) if self._overload_ctl.overload else chunk

    def _prefill_chunk_hint(self) -> Optional[int]:
        return self._effective_chunk()

    def _start_prefill(self) -> None:
        """Reserve a slot and open a PrefillTask for the queue head."""
        if self._pf is not None or not self.queue:
            return
        s = self._free_slot()
        if s is None:
            return
        req = self._pop_eligible()
        if req is None:  # whole queue gated by retry backoff
            return
        self._occupy_slot(s, req)
        self._pf = PrefillTask(
            req=req,
            slot=s,
            chunks=chunk_plan(len(req.prompt), self._effective_chunk()),
            cache=self.api.init_cache(1, self.max_len),
        )

    def _abort_inflight(self) -> None:
        self._pf = None

    def _abort_slot(self, s: int) -> None:
        # slot may be reserved by the in-flight prefill (cancel / expiry /
        # quarantine mid-prefill): drop the task with it
        if self._pf is not None and self._pf.slot == s:
            self._pf = None
        super()._abort_slot(s)

    def _schedule(self) -> str:
        self._start_prefill()
        # graceful degradation: under overload, protect running requests'
        # TPOT -- force decode-priority regardless of the configured policy
        policy = "decode" if self._overload_ctl.overload else self.sched.policy
        return next_action(
            policy,
            prefill_ready=self._pf is not None,
            decode_ready=self._decode_ready(),
            last=self._last_action,
        )

    def _step_impl(self, action: str) -> List[Request]:
        """Dispatch one stage (prefill chunk | generate tick); returns
        requests completed by this dispatch."""
        if action == "idle":
            return []
        self._tick += 1
        self._last_action = action
        if action == "prefill":
            return self._prefill_dispatch()
        return self._generate_dispatch()

    # -- stages ------------------------------------------------------------
    def _prefill_dispatch(self) -> List[Request]:
        pf = self._pf
        start, size = pf.next_chunk()
        req = pf.req
        out_dev = None
        with TraceAnnotation("engine.prefill", uid=req.uid, start=start,
                             size=size, fill=start + size,
                             capacity=self.max_len):
            chunk_toks = np.asarray([req.prompt[start : start + size]],
                                    np.int32)
            with self._dispatch():
                if self._prefill_step is not None:
                    logits, pf.cache = self._prefill_step(
                        self.params, jnp.asarray(chunk_toks),
                        jnp.int32(start), pf.cache,
                    )
                else:
                    for j in range(size):
                        logits, pf.cache = self._pf_decode(
                            self.params, jnp.asarray(chunk_toks[:, j : j + 1]),
                            jnp.int32(start + j), pf.cache,
                        )
                pf.advance(size)
                self.counts["prefill_chunks"] += 1
                if pf.complete:
                    # first generated token comes from the final chunk's
                    # logits
                    out_dev, self.key = self._first_token(self.key, logits)
        if out_dev is None:
            return []
        # the finished prefix moves into the reserved decode slot
        with TraceAnnotation("engine.insert", uid=req.uid, slot=pf.slot):
            with self._dispatch():
                self.cache = self._insert_step(
                    self.cache, pf.cache, jnp.int32(pf.slot)
                )
            self.counts["inserts"] += 1
        with TraceAnnotation("engine.sync"):
            out = np.asarray(out_dev)  # the one host sync
        with TraceAnnotation("engine.commit"):
            return self._commit_first_token(pf, int(out[0, 0]),
                                            int(out[1, 0]))

    def _commit_first_token(self, pf: PrefillTask, tok: int,
                            flag: int) -> List[Request]:
        req, s = pf.req, pf.slot
        self._pf = None
        if flag:  # poisoned prefill: contain before serving its first token
            dead = self._quarantine(s, req, flag)
            return [] if dead is None else [dead]
        self.slot_pos[s] = pf.done_tokens  # == len(prompt): next write pos
        req.first_token_t = self._clock()
        req.output.append(tok)
        if self._check_done(s, tok, req):
            self._finish(s, req)
            return [req]
        self.next_token[s] = tok
        return []

    def _generate_dispatch(self) -> List[Request]:
        reserved = self._pf.slot if self._pf is not None else None
        live = [s for s, r in enumerate(self.slot_req)
                if r is not None and s != reserved]
        # rows served, and the tokens their cache rows hold after this
        # tick's write (each live row writes at its slot_pos)
        with TraceAnnotation(
            "engine.generate", tick=self._tick, rows=len(live),
            slots=self.n_slots, fill=int(self.slot_pos[live].sum()) + len(live),
            capacity=self.n_slots * self.max_len,
        ):
            fault_slot, fault_val = self._draw_fault()
            tokens, pos = self._device_operands()
            with self._dispatch():
                out, self.key, self.cache = self._decode_step(
                    self.params, tokens, pos, self.cache, self.key,
                    fault_slot, fault_val,
                )
        with TraceAnnotation("engine.sync"):
            out = np.asarray(out)  # the ONE host sync per tick
        self.counts["generate_ticks"] += 1
        with TraceAnnotation("engine.commit"):
            return self._commit_tick(out[0], out[1], reserved)

    def _commit_tick(self, sampled: np.ndarray, flags: np.ndarray,
                     reserved: Optional[int]) -> List[Request]:
        completed: List[Request] = []
        for s, req in enumerate(self.slot_req):
            if req is None or s == reserved:
                continue  # idle or mid-prefill: pad row, output discarded
            if flags[s]:  # guardrail tripped: contain before consuming
                dead = self._quarantine(s, req, int(flags[s]))
                if dead is not None:
                    completed.append(dead)
                continue
            self.slot_pos[s] += 1
            tok = int(sampled[s])
            req.output.append(tok)
            if self._check_done(s, tok, req):
                completed.append(req)
                self._finish(s, req)
            else:
                self.next_token[s] = tok
        return completed

    # -- introspection ------------------------------------------------------
    def compiled_programs(self) -> Dict[str, Any]:
        """Adds the prefill step at one chunk of the configured size."""
        out = super().compiled_programs()
        if self._prefill_step is not None:
            toks = jnp.zeros((1, self.sched.prefill_chunk), jnp.int32)
            with self._dispatch():
                out["prefill"] = self._prefill_step.lower(
                    self.params, toks, jnp.int32(0),
                    self.api.init_cache(1, self.max_len),
                ).compile()
        return out

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        pf = self._pf
        out.update(
            engine="staged",
            policy=self.sched.policy,
            prefill_chunk=self.sched.prefill_chunk,
            counts=dict(self.counts),
            inflight_prefill=None if pf is None else {
                "uid": pf.req.uid,
                "slot": pf.slot,
                "done_tokens": pf.done_tokens,
                "total_tokens": len(pf.req.prompt),
            },
        )
        return out
