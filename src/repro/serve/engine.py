"""Batched serving engine: slot-based continuous batching (lite).

A fixed batch of B slots decodes in lockstep; each slot carries its own
absolute position (per-sequence pos vector — see decode_attention), so a
finished slot can be refilled with a new request without draining the
batch.

Admission is BULK: each admit round gathers a request per free slot and
prefills them all in ONE packed ragged launch (_admit_batch ->
decode.packed_prefill over the core/packing PackedSchedule grid —
sum_r tri(n_r) tiles, no per-request launches, no pad-to-max), then
splices each request's KV rows out of the packed states into its slot's
cache. Which requests ride together is COST-ordered by default: each
round admits the oldest queued request (aging — no starvation), then
fills the remaining free slots alternating the lightest and heaviest
pending by tile count (tri(ceil(S / block)), the packed cost model), so
successive packed rounds equalize total tiles; admit_order="fifo"
restores strict arrival order. The chosen order is exposed per round in
stats["admit_order_log"] / ["admit_round_tiles"]. Architectures with recurrent token mixers (mamba/rwkv) fall back to
the sequential per-token prefill: their state is not splice-able across a
packed concatenation.

This is the TPU-idiomatic middle ground between static batching and paged
attention: contiguous per-slot caches (DMA-friendly, no page tables), with
slot-level admission. Paged KV a la vLLM is GPU-pointer-chasing-shaped and
intentionally NOT ported (DESIGN.md §2).

Request lifecycle hardening (see src/repro/resilience/README.md)
----------------------------------------------------------------
Every round is allowed to FAIL. The engine then walks a declared
degradation ladder instead of crashing:

  admit   packed -> packed_scan -> sequential   (+ traced -> host when a
          member would exceed the certified LTM_TRACED_MAX_LAM envelope)
  decode  packed -> lockstep

Each stage gets bounded retries with seeded exponential backoff + jitter
(RetryPolicy); each ladder transition is asserted registered against
repro.resilience.faults.LADDERS, counted in ``launches_degraded_total``,
and emitted as a ``degrade`` trace event. Per-request deadlines/TTLs are
checked every loop tick on the engine's clock (injectable — a
VirtualClock makes the whole lifecycle deterministic under test);
overload shedding reuses the tri(n) admission cost ordering and never
sheds the queue head, so backpressure stays starvation-free. A cheap
NaN/Inf guard inspects every round's emitted logits: a poisoned slot is
QUARANTINED (``slots_quarantined_total`` + a ``quarantine`` trace event)
and its request replayed deterministically — re-prefilled from
prompt + tokens-already-emitted into a healthy slot, which reconstructs
the exact pre-fault state because decode is deterministic (greedy decode
therefore resumes token-identically; sampled decode stays replayable but
quarantine reorders RNG-key consumption). A round that fails past the
last ladder rung is attributed to the responsible request uids in
stats["failures"] and the engine keeps serving the unaffected slots —
every submitted request ends in exactly one terminal status
(done / shed / deadline_miss / failed), never silently dropped.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mapping as M
from repro.kernels.tri_attn import ops as attn_ops
from repro.models import model as MD
from repro.obs import metrics as MET
from repro.obs import schema as SCH
from repro.obs import sinks as SK
from repro.obs import trace as TR
from repro.resilience import faults as F
from repro.resilience import health as H
from repro.serve import decode as D
from repro.serve import kv_cache as KV


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle (terminal statuses: done | shed | deadline_miss | failed)
    status: str = "queued"
    deadline_s: Optional[float] = None
    submitted_at: float = 0.0
    replays: int = 0
    error: Optional[str] = None

    @property
    def feed(self) -> np.ndarray:
        """Tokens to prefill on (re)admission: the prompt plus everything
        already emitted. Quarantine replay prefills on this to re-derive
        the exact pre-fault cache state (decode is deterministic)."""
        if not self.out:
            return self.prompt
        return np.concatenate([self.prompt,
                               np.asarray(self.out, np.int32)])


# Decode auto-mode cost model: the packed decode round pays a per-tile
# scheduling overhead (member search + emit gating) that the lockstep
# fused einsum does not — measured at ~2.3x per tile on the bench_packed
# harness (CPU scan and interpreted pallas agree within noise). "auto"
# therefore takes the packed path only when the lockstep pad-to-max
# waste exceeds that premium: RATIO * sum(tiles) < B * max(tiles).
# In particular a uniform all-live batch (skew=1, equal grids) always
# stays lockstep — the old any-skew test sent it packed and lost 2.3x.
PACKED_TILE_COST_RATIO = 2.3

# The spans of one engine round (obs.trace), outermost first. Every host
# phase of Engine.round() runs under one of them, so the idle gaps of a
# device profile fall under the phase that left the device waiting.
# engine.to_host wraps each blocking device-to-host read: its count in
# span_ms{name=engine.to_host} is the number of host syncs.
SPANS = ("engine.round", "engine.schedule", "engine.admit_batch",
         "engine.admit_commit", "engine.decode_plan", "engine.decode_round",
         "engine.fused_step", "engine.guard", "engine.sample",
         "engine.commit", "engine.to_host")

_f32_copy = functools.partial(np.array, dtype=np.float32)


def _poison(states):
    """NaN in every float leaf: the corruption a FaultPlan injects."""
    return jax.tree.map(
        lambda x: jnp.full_like(x, jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, states)


class EngineStepError(RuntimeError):
    """A round failed past the last rung of its degradation ladder."""

    def __init__(self, phase: str, rnd: int, cause: BaseException):
        super().__init__(f"{phase} round {rnd} failed after retries and "
                         f"degradation: {type(cause).__name__}: {cause}")
        self.phase, self.round, self.cause = phase, rnd, cause


class Engine:
    """In-process engine; submit() then run() until drained."""

    def __init__(self, params, cfg, *, slots: int = 4, max_len: int = 512,
                 cache_dtype=None, temperature: float = 0.0,
                 seed: int = 0, prefill_mode: str = "packed",
                 prefill_block: int = 16, prefill_impl: Optional[str] = None,
                 prefill_bucket: int = 0, decode_mode: str = "auto",
                 decode_block: int = 16, decode_impl: Optional[str] = None,
                 step_mode: str = "split", auto_cost_measure: bool = False,
                 admit_order: str = "cost", stats_log_rounds: int = 1024,
                 fault_plan: Optional[F.FaultPlan] = None, clock=None,
                 retry: Optional[F.RetryPolicy] = None,
                 deadline_s: Optional[float] = None,
                 max_queue_tiles: int = 0, quarantine_rounds: int = 8,
                 traced_max_lam: Optional[int] = None,
                 guard_output: bool = True,
                 escalate_step_errors: bool = False):
        # unset choices follow what the engine can observe: the cache holds
        # the model's own dtype, and attention runs the Pallas kernels on a
        # TPU and the scan mirror elsewhere (ops.platform_impl)
        if cache_dtype is None:
            cache_dtype = MD.param_dtype(cfg)
        # ctor kwargs as REQUESTED (pre-downgrade), for snapshot/restore;
        # fault_plan/clock/retry are runtime harness, supplied at restore.
        self._init_kw = dict(
            slots=slots, max_len=max_len, cache_dtype=cache_dtype,
            temperature=temperature, seed=seed, prefill_mode=prefill_mode,
            prefill_block=prefill_block, prefill_impl=prefill_impl,
            prefill_bucket=prefill_bucket, decode_mode=decode_mode,
            decode_block=decode_block, decode_impl=decode_impl,
            step_mode=step_mode, auto_cost_measure=auto_cost_measure,
            admit_order=admit_order, stats_log_rounds=stats_log_rounds,
            deadline_s=deadline_s, max_queue_tiles=max_queue_tiles,
            quarantine_rounds=quarantine_rounds,
            traced_max_lam=traced_max_lam, guard_output=guard_output)
        self.params, self.cfg = params, cfg
        self.B, self.max_len = slots, max_len
        prefill_impl = prefill_impl or attn_ops.platform_impl()
        decode_impl = decode_impl or attn_ops.platform_impl()
        self.cache = MD.init_cache(cfg, slots, max_len, cache_dtype)
        # slot positions live on the host: every writer below rebinds a new
        # array (a launch or a snapshot may still hold the old one), and
        # every launch takes it as an argument, so no round reads it back
        self.pos = np.zeros((slots,), np.int32)
        self.last_tok = jnp.zeros((slots, 1), jnp.int32)
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.remaining = np.zeros((slots,), np.int64)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.temperature = temperature
        self.key = jax.random.key(seed)
        # packed ragged prefill needs splice-able (attention) token mixers;
        # recurrent archs keep the sequential per-token path.
        assert prefill_mode in ("packed", "sequential")
        attn_only = all(k == "attn" for k in cfg.layer_kinds)
        self.prefill_mode = prefill_mode if attn_only else "sequential"
        self.prefill_block = prefill_block
        self.prefill_impl = prefill_impl
        # length-bucketing quantum for the packed forward's static shapes:
        # 0 = exact block padding (one compile per distinct length tuple);
        # set >0 under compile-bound traffic (see decode.packed_prefill).
        self.prefill_bucket = prefill_bucket
        # packed mixed-position decode: position-skewed rounds go through
        # decode.decode_step_packed ("auto"); uniform all-live rounds keep
        # the lockstep einsum (one fused op, no per-tile bookkeeping).
        # Recurrent archs auto-fall back to lockstep like prefill does.
        assert decode_mode in ("auto", "packed", "lockstep")
        self.decode_mode = decode_mode if attn_only else "lockstep"
        self.decode_impl = decode_impl
        # fused continuous batching: admits AND live decode slots advance
        # in ONE mixed packed launch per engine step (_step_fused). Needs
        # splice-able attention mixers, same as packed prefill.
        assert step_mode in ("split", "fused")
        self.step_mode = step_mode if attn_only else "split"
        # auto-mode cost model: the constant PACKED_TILE_COST_RATIO, or —
        # opt-in — a measured per-mode EMA of seconds/tile from this
        # engine's own rounds (only trusted once both modes have run).
        self.auto_cost_measure = auto_cost_measure
        self._mode_cost = {"packed": None, "lockstep": None}
        # attention KV geometry, read off the ACTUAL cache leaves (the
        # same source decode_step_packed uses — kv_len clamps can never
        # drift from the real buffer size); recurrent-only archs have no
        # KV leaves and only ever take the lockstep path, so the window
        # formula stands in for their (unused) stats bookkeeping. The
        # decode tile edge must divide S_cache (same normalization as
        # decode_step_packed, pre-applied so stats use the real edge).
        self.s_cache = D._attn_cache_len(cfg, self.cache) if any(
            k == "attn" for k in cfg.layer_kinds) else max(
            1, max_len if cfg.sliding_window is None
            else min(cfg.sliding_window, max_len))
        blk = min(decode_block, self.s_cache)
        while self.s_cache % blk:
            blk //= 2
        self.decode_block = blk
        # cost-model-driven admission: order the queue by per-request
        # prefill tile count (tri(n_r) — the packed launch's exact cost
        # model) so successive packed rounds equalize total tiles instead
        # of inheriting arrival-order lumps; "fifo" keeps strict arrival
        # order. The chosen order is exposed per round in stats.
        assert admit_order in ("cost", "fifo")
        self.admit_order = admit_order
        # -- resilience harness ------------------------------------------
        # clock is injectable: a resilience.faults.VirtualClock makes
        # deadlines, backoff and straggler delays deterministic under
        # test; anything with a .sleep(dt) method is "slept" through it.
        self.clock = clock if clock is not None else time.monotonic
        self.fault_plan = fault_plan
        self.retry = retry if retry is not None else F.RetryPolicy(seed=seed)
        self.default_deadline_s = deadline_s
        self.max_queue_tiles = max_queue_tiles
        self.quarantine_rounds = quarantine_rounds
        self._traced_max_lam = (M.LTM_TRACED_MAX_LAM if traced_max_lam
                                is None else traced_max_lam)
        self.guard_output = guard_output
        # fleet-replica mode (runtime harness, like fault_plan/clock —
        # NOT part of _init_kw): instead of absorbing a terminal round
        # failure in-engine (fail the round's requests, quarantine a
        # poisoned slot), RAISE it so the owning Fleet can snapshot the
        # replica and migrate its requests token-identically. Requests
        # not yet committed to a slot are requeued at the head before the
        # raise, so the snapshot the fleet captures accounts for every
        # request exactly once.
        self.escalate_step_errors = escalate_step_errors
        self.quarantined: Dict[int, int] = {}  # slot -> release round
        self._rolling = cfg.sliding_window is not None
        self._round_watch = H.RoundWatch()
        self._admit_round_idx = 0
        self._decode_round_idx = 0
        # distinct fused packing templates this engine has compiled under:
        # {(padded-length tuple, capacity)} — the compile-footprint record
        # the snapshot persists (satellite of the bucketing story: the
        # set is bounded by prefill_bucket, and a restored engine knows
        # which programs its predecessor already paid for).
        self.fused_templates: set = set()
        # observability: ONE packed launch per admit round (prefill) and
        # per decode round; prefill vs decode launches counted apart, plus
        # per-round tile accounting for the packed-vs-padded claim.
        # Counters live in a per-engine obs registry (mirrored into the
        # process-global registry as engine_* so metrics.json aggregates
        # them); the per-round admit logs are RingLog-capped at
        # ``stats_log_rounds`` (default 1024) so long-running engines stay
        # O(cap) memory — totals stay exact via RingLog.total_appended,
        # surfaced as stats["admit_rounds_total"] / ["admit_log_dropped"].
        # The legacy ``stats`` dict is now a read-only property view.
        self.registry = MET.Registry("engine")
        # admit_order_log[r] is round r's admitted (uid, tiles) pairs in
        # launch order; admit_round_tiles[r] its packed tile total.
        self._admit_order_log = MET.RingLog(maxlen=stats_log_rounds)
        self._admit_round_tiles = MET.RingLog(maxlen=stats_log_rounds)
        self._failures = MET.RingLog(maxlen=stats_log_rounds)
        self._decode = jax.jit(
            lambda p, c, t, pos: MD.decode_step(p, cfg, c, t, pos))

    # -- telemetry -----------------------------------------------------------
    _COUNTERS = ("prefill_launches", "prefill_requests", "prefill_tokens",
                 "admit_rounds", "decode_rounds", "decode_packed_launches",
                 "decode_lockstep_launches", "decode_tiles_packed",
                 "decode_tiles_padded", "fused_rounds", "fused_launches",
                 "fused_fallbacks", "fused_tiles")

    def _inc(self, name: str, value: int = 1):
        """Count into the per-engine registry AND the process-global one
        (prefixed engine_* there, so metrics.json aggregates every engine
        without label collisions)."""
        self.registry.counter_inc(name, value)
        MET.counter_inc("engine_" + name, value)

    def _inc_res(self, name: str, value: int = 1):
        """Resilience counters keep their CANONICAL *_total names in both
        the per-engine registry and the process-global one — these are
        the issue-facing names schema.RESILIENCE_COUNTERS declares and
        metrics.json carries."""
        self.registry.counter_inc(name, value)
        MET.counter_inc(name, value)

    def _set_pos(self, slot: int, value: int):
        """Rebind ``pos`` to a copy with ``slot`` set: the array a launch or
        a snapshot was handed is never written in place."""
        pos = self.pos.copy()
        pos[slot] = value
        self.pos = pos

    def _to_host(self, x, what: str, read=np.asarray):
        """Every blocking device-to-host read of a round goes through here,
        as ``read(x)`` under an ``engine.to_host`` span (attrs ``what``,
        ``nbytes``): the count of those spans is the round's host syncs."""
        with TR.span("engine.to_host", what=what, nbytes=int(x.nbytes)):
            return read(x)

    @property
    def stats(self) -> dict:
        """Read-only compat view of the registry-backed counters (the old
        ad-hoc dict, plus ring-buffer totals). Mutating the returned dict
        does NOT feed back into the engine."""
        st = {name: int(self.registry.counter_value(name))
              for name in self._COUNTERS}
        for name in SCH.RESILIENCE_COUNTERS:
            st[name] = int(self.registry.counter_value(name))
        st["admit_order_log"] = self._admit_order_log.items()
        st["admit_round_tiles"] = self._admit_round_tiles.items()
        st["admit_rounds_total"] = self._admit_order_log.total_appended
        st["admit_log_dropped"] = self._admit_order_log.dropped
        # per-step failures attributed to the responsible request uid
        st["failures"] = self._failures.items()
        return st

    def report(self) -> Dict[int, dict]:
        """Explicit per-request lifecycle report. Every submitted request
        appears with its status (queued / running / done / shed /
        deadline_miss / failed), token count, replay count, and error —
        shed and deadline-missed requests are REPORTED here, never
        silently dropped."""
        reqs = (list(self.finished)
                + [r for r in self.slot_req if r is not None]
                + list(self.queue))
        return {r.uid: {"status": r.status, "tokens": len(r.out),
                        "replays": r.replays, "error": r.error}
                for r in reqs}

    # -- resilience plumbing -------------------------------------------------
    def _sleep(self, dt: float):
        """Advance the injectable clock (VirtualClock.sleep) or really
        sleep, capped so a mis-sized backoff cannot stall a live engine."""
        if dt <= 0.0:
            return
        sleeper = getattr(self.clock, "sleep", None)
        if sleeper is not None:
            sleeper(dt)
        else:
            time.sleep(min(dt, self.retry.cap_s))

    def _finish(self, req: Request, status: str,
                error: Optional[str] = None):
        req.status = status
        req.done = True
        req.error = error
        self.finished.append(req)

    def _record_failure(self, req: Request, phase: str, rnd: int,
                        err: BaseException):
        msg = f"{type(err).__name__}: {err}"
        self._finish(req, "failed", error=msg)
        self._inc_res("requests_failed_total")
        self._failures.append({"uid": req.uid, "phase": phase,
                               "round": rnd, "error": msg})

    def _degrade(self, phase: str, rnd: int, frm: str, to: str,
                 reason: str):
        """One rung down the declared ladder: counted, traced, and
        runtime-checked against the transition registry (an unregistered
        transition is a bug — the resilience lint pass proves the
        registry matches schema.DEGRADE_STAGES)."""
        assert F.is_registered_transition(phase, frm, to), (
            f"unregistered degradation {phase}: {frm} -> {to}; declare it "
            f"in repro.resilience.faults.LADDERS")
        self._inc_res("launches_degraded_total")
        if SK.trace_enabled():
            SK.emit_event({"type": "degrade", "phase": phase, "from": frm,
                           "to": to, "round": rnd, "reason": reason[:200]})

    def _attempt(self, fn, n_affected: int):
        """Run one ladder stage with bounded retries + seeded backoff.
        Returns (ok, result, err)."""
        err: Optional[BaseException] = None
        for attempt in range(self.retry.max_retries + 1):
            try:
                return True, fn(attempt), None
            except Exception as e:  # noqa: BLE001 — hardening boundary
                if self.escalate_step_errors and \
                        isinstance(e, (EngineStepError, F.PoisonedOutput)):
                    # fleet replica: a nested terminal failure or a
                    # poisoned round is not retried here — it escalates
                    # so the fleet can quarantine + migrate.
                    raise
                err = e
                if attempt < self.retry.max_retries:
                    self._inc_res("requests_retried_total", n_affected)
                    self._sleep(self.retry.delay(attempt))
        return False, None, err

    def _run_ladder(self, phase: str, rnd: int, stages: List[str],
                    runner, n_affected: int):
        """Walk the phase's degradation ladder: bounded retries within a
        stage, a registered degrade transition between stages. Returns
        (result, stage) or raises EngineStepError carrying the cause."""
        err: Optional[BaseException] = None
        for si, stage in enumerate(stages):
            ok, result, err = self._attempt(
                lambda a, s=stage: runner(s, a), n_affected)
            if ok:
                return result, stage
            if si + 1 < len(stages):
                self._degrade(phase, rnd, stage, stages[si + 1],
                              reason=f"{type(err).__name__}: {err}")
        raise EngineStepError(phase, rnd, err)

    # -- admission -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int, uid: int,
               deadline_s: Optional[float] = None):
        prompt = np.asarray(prompt, np.int32)
        if prompt.size == 0:
            raise ValueError(f"request {uid}: empty prompt")
        if prompt.size > self.max_len:
            raise ValueError(
                f"request {uid}: prompt of {prompt.size} tokens exceeds "
                f"max_len={self.max_len} — its KV splice would overflow "
                f"the slot cache (raise max_len or truncate)")
        req = Request(uid, prompt, max_new,
                      deadline_s=(self.default_deadline_s
                                  if deadline_s is None else deadline_s),
                      submitted_at=float(self.clock()))
        self.queue.append(req)
        self._shed_overload()

    def _shed_overload(self):
        """Overload shedding on the tri(n) cost ordering: while the
        queue's packed-prefill tile total exceeds ``max_queue_tiles``,
        shed the HEAVIEST request that is not the queue head. The aging
        guarantee (the head always rides the next admit round) is what
        keeps backpressure starvation-free — so the head is never shed,
        however heavy, and shedding is deterministic in arrival order."""
        if not self.max_queue_tiles:
            return
        while len(self.queue) > 1 and \
                sum(self._prefill_tiles(r) for r in self.queue) \
                > self.max_queue_tiles:
            victim_i = max(range(1, len(self.queue)),
                           key=lambda i: (self._prefill_tiles(self.queue[i]),
                                          i))
            victim = self.queue.pop(victim_i)
            self._inc_res("requests_shed_total")
            self._finish(victim, "shed", error=(
                f"shed: queue over capacity ({self.max_queue_tiles} "
                f"tiles) and this was the heaviest non-head request"))

    def _expire_deadlines(self):
        """TTL sweep on the engine clock: queued AND running requests past
        their deadline are retired explicitly (status deadline_miss, the
        tokens emitted so far preserved) — a request never occupies a
        slot or a queue position beyond its deadline."""
        now = float(self.clock())

        def missed(req):
            return req.deadline_s is not None and \
                now - req.submitted_at > req.deadline_s

        for req in [r for r in self.queue if missed(r)]:
            self.queue.remove(req)
            self._miss(req, now)
        for slot in range(self.B):
            req = self.slot_req[slot]
            if req is not None and missed(req):
                self.slot_req[slot] = None
                self._miss(req, now)

    def _miss(self, req: Request, now: float):
        self._inc_res("deadline_misses_total")
        self._finish(req, "deadline_miss", error=(
            f"deadline {req.deadline_s}s exceeded after "
            f"{now - req.submitted_at:.3f}s"))

    def _prefill_into_slot(self, slot: int, req: Request):
        """Run the request's feed through decode steps to fill the slot
        cache — the sequential HOST-map path (also the last rung of the
        admit ladder and the traced-envelope fallback).

        Single-slot prefill via the decode path keeps the engine simple and
        exact; bulk prefill via prefill_cache covers the offline path. Other
        slots' cache entries are masked back to their previous values —
        recurrent states (mamba/rwkv) are NOT idempotent under replay."""
        b = self.B
        onehot = jnp.arange(b) == slot  # (B,)

        def merge(new, old):
            m = onehot.reshape((1, b) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)

        toks = req.feed
        for t_idx, tok in enumerate(toks):
            tok_b = self.last_tok.at[slot, 0].set(int(tok))
            self._set_pos(slot, t_idx)
            logits, cache = self._decode(self.params, self.cache, tok_b,
                                         self.pos)
            self.cache = jax.tree.map(merge, cache, self.cache)
            self.last_tok = tok_b
        self._set_pos(slot, len(toks) - 1)
        self.slot_req[slot] = req
        self.remaining[slot] = req.max_new - len(req.out)
        self._inc("prefill_launches", len(toks))
        self._inc("prefill_requests")
        self._inc("prefill_tokens", len(toks))

    def _splice_slot(self, slot: int, states, start: int, length: int):
        """Validated splice of one request's packed KV rows into a slot
        cache — bounds checking lives in serve/kv_cache.splice_slot."""
        self.cache = KV.splice_slot(self.cache, slot, states, start,
                                    length, rolling=self._rolling)

    def _admit_stages(self, rnd: int, reqs: List[Request]) -> List[str]:
        """The admit round's degradation ladder, from the configured
        fast path down to the sequential host path."""
        if self.prefill_mode != "packed":
            return ["sequential"]
        if not D.traced_prefill_ok([len(r.feed) for r in reqs],
                                   self.prefill_block,
                                   self._traced_max_lam):
            # certified-envelope guard: the traced isqrt block map is only
            # exact up to LTM_TRACED_MAX_LAM; past it, take the host map.
            self._degrade("admit", rnd, "traced", "host", reason=(
                "member exceeds the certified traced-isqrt envelope "
                f"(traced_max_lam={self._traced_max_lam})"))
            return ["sequential"]
        stages = ["packed"]
        if self.prefill_impl == "pallas":
            stages.append("packed_scan")
        stages.append("sequential")
        return stages

    def _admit_packed(self, pairs, rnd: int, impl: str):
        """Bulk admission: ONE packed ragged-prefill launch for every
        (slot, request) pair, then per-slot KV splicing — committed only
        after the output guard passes, so a retried round never leaves
        half-spliced state behind."""
        if self.fault_plan is not None:
            self._sleep(self.fault_plan.maybe_fail("admit", rnd))
        prompts = [req.feed for _, req in pairs]
        with TR.span("engine.admit_batch", requests=len(pairs)) as sp:
            _, starts, lens, _, states = D.packed_prefill(
                self.params, self.cfg, prompts, block=self.prefill_block,
                attn_impl=impl, bucket=self.prefill_bucket)
            sp.attach(states)
        with TR.span("engine.admit_commit"):
            if self.fault_plan is not None and \
                    self.fault_plan.poisons_admit(rnd):
                # injected corruption lands at the host boundary the guard
                # below inspects — the detection path is the real one.
                states = _poison(states)
            if self.guard_output and not D.states_finite(states,
                                                         self._to_host):
                raise F.PoisonedOutput(
                    f"admit round {rnd}: non-finite packed prefill states")
            self._inc("prefill_launches")
            self._inc("prefill_requests", len(pairs))
            self._inc("prefill_tokens", sum(lens))
            for (slot, req), start, length in zip(pairs, starts, lens):
                self._splice_slot(slot, states, start, length)
                self.last_tok = self.last_tok.at[slot, 0].set(
                    int(req.feed[-1]))
                self._set_pos(slot, length - 1)
                self.slot_req[slot] = req
                self.remaining[slot] = req.max_new - len(req.out)

    def _admit_sequential(self, pairs, rnd: int):
        """Per-request sequential prefill (host-map path): each request
        is retried and, on exhaustion, failed INDIVIDUALLY — one bad
        request cannot take down its round-mates."""
        with TR.span("engine.admit_batch", requests=len(pairs),
                     mode="sequential"):
            for member, (slot, req) in enumerate(pairs):
                def one(attempt, s=slot, r=req, m=member):
                    if self.fault_plan is not None:
                        self._sleep(self.fault_plan.maybe_fail(
                            "admit", rnd, member=m))
                    self._prefill_into_slot(s, r)

                ok, _, err = self._attempt(one, n_affected=1)
                if not ok:
                    if self.escalate_step_errors:
                        # last rung of the admit ladder exhausted on a
                        # fleet replica: the engine is out of fallbacks.
                        raise EngineStepError("admit", rnd, err)
                    self._record_failure(req, "admit", rnd, err)

    def _prefill_tiles(self, req: Request) -> int:
        """Packed-prefill cost model for one request: tri(ceil(S / block))
        — exactly the blocks its member contributes to the admit round's
        packed grid (core/packing: num_blocks is the sum of member
        triangles). S counts the feed (prompt + replayed tokens)."""
        return M.tri(-(-len(req.feed) // self.prefill_block))

    def _pick_requests(self, take: int) -> List[Request]:
        """Pop ``take`` queued requests for this admit round.

        "cost": the OLDEST queued request always rides (aging guarantee —
        every admit round retires the head of the queue, so no request is
        starved however its tile count sits between the ends), then the
        remaining slots alternate the lightest / heaviest pending so each
        packed round's total tiles lands near the queue mean — successive
        rounds equalize instead of inheriting arrival-order lumps (one
        round all-long, the next all-short). Ties keep arrival order.
        "fifo": strict arrival order.
        """
        if self.admit_order != "cost":
            return [self.queue.pop(0) for _ in range(take)]
        tiles = [self._prefill_tiles(r) for r in self.queue]
        heavy = iter(sorted(range(len(tiles)), key=lambda i: (-tiles[i], i)))
        light = iter(sorted(range(len(tiles)), key=lambda i: (tiles[i], i)))
        picked, used = [0], {0}  # aging: head of queue always admitted
        for t in range(take - 1):
            ends = light if t % 2 == 0 else heavy
            i = next(j for j in ends if j not in used)
            picked.append(i)
            used.add(i)
        reqs = [self.queue[i] for i in picked]
        for i in sorted(picked, reverse=True):
            self.queue.pop(i)
        return reqs

    def _release_quarantine(self):
        """Return quarantined slots to service once their hold expires —
        and immediately when the engine would otherwise deadlock (queue
        waiting, nothing running, every slot quarantined)."""
        rnd = self._decode_round_idx
        for slot in [s for s, rel in list(self.quarantined.items())
                     if rnd >= rel]:
            del self.quarantined[slot]
        if self.queue and self.quarantined \
                and not any(r is not None for r in self.slot_req) \
                and len(self.quarantined) >= self.B:
            first = min(self.quarantined,
                        key=lambda s: (self.quarantined[s], s))
            del self.quarantined[first]

    def _take_admits(self) -> List[tuple]:
        """Release expired quarantines and pop a queued request for every
        free slot: this round's (slot, request) admits."""
        self._release_quarantine()
        free = [s for s in range(self.B) if self.slot_req[s] is None
                and s not in self.quarantined]
        take = min(len(free), len(self.queue))
        if not take:
            return []
        reqs = self._pick_requests(take)
        for req in reqs:
            req.status = "running"
        return list(zip(free, reqs))

    def _log_admit(self, reqs: List[Request]):
        self._inc("admit_rounds")
        self._admit_order_log.append(
            [(r.uid, self._prefill_tiles(r)) for r in reqs])
        self._admit_round_tiles.append(
            sum(self._prefill_tiles(r) for r in reqs))

    def _plan_admit(self, pairs):
        """Number and log a split admit round; its ladder. None when
        there is nothing to admit."""
        if not pairs:
            return None
        reqs = [req for _, req in pairs]
        self._log_admit(reqs)
        rnd = self._admit_round_idx
        self._admit_round_idx += 1
        return pairs, rnd, self._admit_stages(rnd, reqs)

    def _admit(self):
        with TR.span("engine.schedule"):
            plan = self._plan_admit(self._take_admits())
        self._run_admit(plan)

    def _run_admit(self, plan):
        """Launch a planned admit round down its degradation ladder."""
        if plan is None:
            return
        pairs, rnd, stages = plan

        def runner(stage, attempt):
            if stage == "sequential":
                return self._admit_sequential(pairs, rnd)
            impl = "scan" if stage == "packed_scan" else self.prefill_impl
            return self._admit_packed(pairs, rnd, impl)

        try:
            self._run_ladder("admit", rnd, stages, runner,
                             n_affected=len(pairs))
        except (EngineStepError, F.PoisonedOutput) as e:
            if self.escalate_step_errors:
                # fleet replica: requeue the round's uncommitted requests
                # at the head (committed slots ride the snapshot as
                # in-flight) and hand the failure to the fleet.
                requeue = [req for slot, req in pairs
                           if self.slot_req[slot] is not req]
                for req in requeue:
                    req.status = "queued"
                self.queue[0:0] = requeue
                raise
            # even the sequential rung raised for the whole round: fail
            # every request of the round explicitly and keep serving.
            for slot, req in pairs:
                if self.slot_req[slot] is req:
                    self.slot_req[slot] = None
                self._record_failure(req, "admit", rnd,
                                     getattr(e, "cause", e))

    # -- decode loop ---------------------------------------------------------
    def _decode_stage(self, stage: str, rnd: int, live, kv_lens):
        """One decode-round launch at a given ladder stage."""
        if self.fault_plan is not None:
            self._sleep(self.fault_plan.maybe_fail("decode", rnd))
        if stage == "packed":
            with TR.span("engine.decode_round", mode="packed",
                         live=len(live)) as sp:
                logits, cache, info = D.decode_step_packed(
                    self.params, self.cfg, self.cache, self.last_tok,
                    self.pos, kv_lens, live, block=self.decode_block,
                    impl=self.decode_impl)
                sp.attach(logits)
            if info.get("rebucketed"):
                self._degrade("capacity", rnd, "requested", "rebucketed",
                              reason=(f"pinned capacity below the round's "
                                      f"{info['tiles']} live tiles"))
        else:
            with TR.span("engine.decode_round", mode="lockstep",
                         live=len(live)) as sp:
                logits, cache = self._decode(self.params, self.cache,
                                             self.last_tok, self.pos)
                sp.attach(logits)
        return logits, cache

    def step(self):
        """One decode round across all live slots — packed (mixed-position,
        each slot over its own valid KV prefix) when the batch is
        position-skewed or has retired slots, lockstep otherwise. Runs
        under the decode degradation ladder; emitted logits pass the
        NaN/Inf guard before any state commits, and poisoned slots are
        quarantined + replayed instead of emitting garbage."""
        with TR.span("engine.decode_plan"):
            active = np.array([r is not None for r in self.slot_req])
            if not active.any():
                return
            live = [s for s in range(self.B) if active[s]]
            kv_lens = [int(min(self.pos[s] + 1, self.s_cache)) for s in live]
            # round geometry (recorded every round, whichever path runs):
            # what the packed grid covers vs what pad-to-max lockstep would.
            tiles = [-(-kl // self.decode_block) for kl in kv_lens]
            # COST CROSSOVER (not the old any-skew test): the packed round
            # does RATIO times more work per tile than lockstep's fused
            # einsum, so "auto" goes packed only when pad-to-max waste
            # beats that premium. Lockstep always launches the full batch
            # (B * max tiles), so mild skew — or a uniform batch, where the
            # old test already lost 2.3x by going packed — stays lockstep.
            ratio = PACKED_TILE_COST_RATIO
            if self.auto_cost_measure and all(self._mode_cost.values()):
                ratio = (self._mode_cost["packed"]
                         / self._mode_cost["lockstep"])
            use_packed = self.decode_mode == "packed" or (
                self.decode_mode == "auto"
                and ratio * sum(tiles) < self.B * max(tiles))
            self._inc("decode_rounds")
            self._inc("decode_tiles_packed", sum(tiles))
            self._inc("decode_tiles_padded", len(live) * max(tiles))
            rnd = self._decode_round_idx
            self._decode_round_idx += 1
            stages = ["packed", "lockstep"] if use_packed else ["lockstep"]
        t0 = float(self.clock())
        try:
            (logits, cache), stage = self._run_ladder(
                "decode", rnd, stages,
                lambda s, a: self._decode_stage(s, rnd, live, kv_lens),
                n_affected=len(live))
        except EngineStepError as e:
            if self.escalate_step_errors:
                # fleet replica: nothing committed this round — the live
                # slots ride the snapshot as in-flight and migrate.
                raise
            # unrecoverable round: attribute the failure to every live
            # request uid, free the slots, keep the engine serving.
            for slot in live:
                req = self.slot_req[slot]
                self.slot_req[slot] = None
                self._record_failure(req, "decode", rnd, e.cause)
            return
        self._inc("decode_packed_launches" if stage == "packed"
                  else "decode_lockstep_launches")
        dur = float(self.clock()) - t0
        if self._round_watch.observe(dur):
            self._inc_res("rounds_straggler_total")
        if self.auto_cost_measure:
            done = sum(tiles) if stage == "packed" else self.B * max(tiles)
            per_tile = dur / max(1, done)
            prev = self._mode_cost[stage]
            self._mode_cost[stage] = per_tile if prev is None \
                else 0.8 * prev + 0.2 * per_tile
        with TR.span("engine.guard"):
            bad = self._guard(logits, rnd, live, "decode")
        with TR.span("engine.sample"):
            self.key, k = jax.random.split(self.key)
            nxt = D.sample_logits(k, logits[:, 0],
                                  temperature=self.temperature,
                                  vocab_size=self.cfg.vocab_size)
            nxt_np = self._to_host(nxt, "tokens")
        with TR.span("engine.commit"):
            self.cache = cache
            adv = active.copy()
            for slot in bad:
                adv[slot] = False  # quarantined: state reset at readmission
            self.pos = self.pos + adv.astype(np.int32)
            self.last_tok = nxt[:, None]
            for slot in range(self.B):
                req = self.slot_req[slot]
                if req is None:
                    continue
                req.out.append(int(nxt_np[slot]))
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0 or \
                        int(self.pos[slot]) >= self.max_len - 1:
                    self._finish(req, "done")
                    self.slot_req[slot] = None  # refilled next admit

    def _guard(self, logits, rnd: int, live, kind: str) -> List[int]:
        """The NaN/Inf guard at the host boundary (where injected poison
        lands too): the live slots whose emitted logits are non-finite,
        quarantined, their requests queued for replay. ``logits`` is
        (B, V), or (B, 1, V) as a decode step returns it."""
        bad: List[int] = []
        if self.guard_output or self.fault_plan is not None:
            if logits.ndim == 3:
                logits = logits[:, 0]
            logits_np = self._to_host(logits, "logits", _f32_copy)
            if self.fault_plan is not None:
                for s in self.fault_plan.poison_slots(rnd, live):
                    logits_np[s] = np.nan
            if self.guard_output:
                bad = D.poisoned_slots(logits_np, live)
        if bad and self.escalate_step_errors:
            # fleet replica: a poisoned round escalates BEFORE any token
            # is appended — the fleet quarantines the whole replica
            # instead of this engine quarantining one slot, and every live
            # request's feed still excludes the poisoned round, so
            # migration re-prefills the exact pre-fault state.
            raise F.PoisonedOutput(
                f"{kind} round {rnd}: non-finite logits in slots {bad}")
        replays: List[Request] = []
        for slot in bad:
            req = self.slot_req[slot]
            self.slot_req[slot] = None
            self.quarantined[slot] = rnd + 1 + self.quarantine_rounds
            req.replays += 1
            req.status = "queued"
            replays.append(req)
            self._inc_res("slots_quarantined_total")
            if SK.trace_enabled():
                SK.emit_event({"type": "quarantine", "slot": slot,
                               "uid": req.uid, "round": rnd,
                               "reason": "nonfinite_logits"})
        if replays:
            # front of the queue: the aging guarantee readmits replayed
            # requests next round, prefilled on prompt + emitted tokens
            # (Request.feed) into a healthy slot.
            self.queue[0:0] = replays
        return bad

    # -- fused continuous batching -------------------------------------------
    def _step_fused(self, pairs):
        """One FUSED engine round: admit ``pairs`` (free slot, request)
        AND advance every live decode slot, in ONE mixed packed
        launch per attention layer (decode.fused_step over the "mixed"
        schedule kind). round() runs rounds with nothing to admit
        through step() — the split decode round is already a single
        launch.

        Any failure inside the fused attempt (injected fault, poisoned
        states, traced-envelope overflow, real launch error) takes the
        registered step: fused -> split rung: the admits are requeued at
        the head and the round re-runs through the split machinery, whose
        own admit/decode ladders then absorb the fault. Greedy decode is
        token-identical either way."""
        reqs = [req for _, req in pairs]
        a_rnd = self._admit_round_idx
        d_rnd = self._decode_round_idx
        with TR.span("engine.decode_plan"):
            admitted = [sl for sl, _ in pairs]
            live = [s for s in range(self.B) if self.slot_req[s] is not None
                    and s not in admitted]
            kv_lens = [int(min(self.pos[s] + 1, self.s_cache)) for s in live]
        self._inc("fused_rounds")
        try:
            if self.fault_plan is not None:
                self._sleep(self.fault_plan.maybe_fail("admit", a_rnd))
                if live:
                    self._sleep(self.fault_plan.maybe_fail("decode", d_rnd))
            prompts = [req.feed for _, req in pairs]
            if not D.traced_prefill_ok([len(p) for p in prompts],
                                       self.decode_block,
                                       self._traced_max_lam):
                raise RuntimeError(
                    "admit member exceeds the certified traced-isqrt "
                    f"envelope (traced_max_lam={self._traced_max_lam})")
            with TR.span("engine.fused_step", requests=len(pairs),
                         live=len(live)) as sp:
                (logits_admit, logits_dec, cache, states, _, starts, lens,
                 info) = D.fused_step(
                    self.params, self.cfg, self.cache, prompts,
                    self.last_tok, self.pos, kv_lens, live,
                    block=self.decode_block, impl=self.decode_impl,
                    bucket=self.prefill_bucket)
                sp.attach(logits_dec)
        except Exception as e:  # noqa: BLE001 — hardening boundary
            return self._fused_fallback(reqs, d_rnd, e)
        # -- commit (the exact split order: admit splice, then decode) ---
        # the states guard belongs to the fused attempt: whatever it
        # raises takes the fused -> split rung, and nothing is committed
        err = None
        with TR.span("engine.admit_commit"):
            try:
                if self.fault_plan is not None and \
                        self.fault_plan.poisons_admit(a_rnd):
                    states = _poison(states)
                if self.guard_output and not D.states_finite(
                        states, self._to_host):
                    raise F.PoisonedOutput(
                        f"fused round {d_rnd}: non-finite packed states")
            except Exception as e:  # noqa: BLE001 — hardening boundary
                err = e
            else:
                self._admit_round_idx += 1
                self._decode_round_idx += 1
                self._log_admit(reqs)
                self._inc("fused_launches")
                self._inc("fused_tiles", info["tiles"])
                self.fused_templates.add(
                    (tuple(info["template"]), int(info["capacity"])))
                self._inc("prefill_requests", len(pairs))
                self._inc("prefill_tokens", sum(lens))
                if live:
                    self._inc("decode_rounds")
                self.cache = cache
                for (slot, req), start, length in zip(pairs, starts, lens):
                    self._splice_slot(slot, states, start, length)
                    self.slot_req[slot] = req
                    self.remaining[slot] = req.max_new - len(req.out)
        if err is not None:
            return self._fused_fallback(reqs, d_rnd, err)
        # decode-half guard, as in step(); the fused cache/splice commits
        # above are discarded with the replica if it escalates — no token
        # was appended this round, so every feed is still pre-fault.
        with TR.span("engine.guard"):
            bad = self._guard(logits_dec, d_rnd, live, "fused")
        # ONE key split per fused round (the admits' first tokens and the
        # decode tokens share it; at temperature=0 both are pure argmax).
        with TR.span("engine.sample"):
            self.key, k = jax.random.split(self.key)
            nxt_np = self._to_host(D.sample_logits(
                k, logits_dec, temperature=self.temperature,
                vocab_size=self.cfg.vocab_size), "tokens")
            adm_np = self._to_host(D.sample_logits(
                k, logits_admit, temperature=self.temperature,
                vocab_size=self.cfg.vocab_size), "admit_tokens")
        with TR.span("engine.commit"):
            new_pos = self.pos.copy()
            new_last = self._to_host(self.last_tok, "last_tok").copy()
            for slot in live:
                if slot in bad:
                    continue
                req = self.slot_req[slot]
                req.out.append(int(nxt_np[slot]))
                new_pos[slot] += 1
                new_last[slot, 0] = int(nxt_np[slot])
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0 or \
                        int(new_pos[slot]) >= self.max_len - 1:
                    self._finish(req, "done")
                    self.slot_req[slot] = None
            for (slot, req), length, tok in zip(pairs, lens, adm_np):
                req.out.append(int(tok))
                new_pos[slot] = length  # the sampled token's position
                new_last[slot, 0] = int(tok)
                self.remaining[slot] -= 1
                if self.remaining[slot] <= 0 or \
                        int(new_pos[slot]) >= self.max_len - 1:
                    self._finish(req, "done")
                    self.slot_req[slot] = None
            self.pos = new_pos
            self.last_tok = jnp.asarray(new_last)

    def _fused_fallback(self, reqs: List[Request], d_rnd: int,
                        err: BaseException):
        """fused -> split: requeue the admits at the head (aging keeps
        them first) and re-run the round through the split ladders, which
        own retries / further degradation for this fault."""
        self._inc("fused_fallbacks")
        self._degrade("step", d_rnd, "fused", "split",
                      reason=f"{type(err).__name__}: {err}")
        for req in reqs:
            req.status = "queued"
        self.queue[0:0] = reqs
        self._admit()
        self.step()

    def idle(self) -> bool:
        """True iff the engine holds no work (empty queue, no live slot)."""
        return not self.queue and all(r is None for r in self.slot_req)

    def round(self):
        """ONE full scheduling round — the unit a fleet driver advances a
        replica by: deadline sweep, then either a fused step or a split
        admit + decode pair, under the spans of SPANS. run() is this in a
        drain loop; a Fleet calls it directly so it can heartbeat/watch
        each replica per round."""
        with TR.span("engine.round"):
            with TR.span("engine.schedule"):
                self._expire_deadlines()
                pairs = self._take_admits()
                plan = None if self.step_mode == "fused" \
                    else self._plan_admit(pairs)
            if self.step_mode == "fused" and pairs:
                self._step_fused(pairs)
                return
            self._run_admit(plan)
            self.step()

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Drive rounds until drained (or max_steps rounds).

        step_mode="fused" folds each round's admission INTO its decode
        launch (_step_fused); "split" keeps the separate packed-admit and
        decode rounds. Returns {uid: tokens} for every request that
        reached a terminal state — including the partial outputs of shed /
        deadline-missed / failed requests (see report() for statuses).
        Per-step failures never abort unaffected slots."""
        for _ in range(max_steps):
            if self.idle():
                break
            self.round()
        return {r.uid: r.out for r in self.finished}

    # -- crash safety --------------------------------------------------------
    def snapshot(self):
        """Serialize slot table + KV cache + RNG/clock state into an
        EngineSnapshot (resilience/snapshot.py)."""
        from repro.resilience import snapshot as SNAP

        return SNAP.snapshot(self)

    @classmethod
    def restore(cls, snap, **overrides):
        """Rebuild an engine from an EngineSnapshot so run() resumes
        token-identically after a crash (params/cfg ride in the snapshot;
        pass fault_plan=/clock=/retry= overrides for the new process)."""
        from repro.resilience import snapshot as SNAP

        return SNAP.restore(snap, **overrides)
