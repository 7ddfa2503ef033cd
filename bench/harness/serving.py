"""Serving cells: the program's ``Engine`` under an open or closed loop.

Set-up makes the weights on the device from the seed, builds one
``Engine`` with the traffic file's arguments, and warms every program the
mix will ask for: the packed prefill of every admit tuple of up to
``warm_admits`` prompts (the engine compiles one program per ordered tuple
of padded prompt lengths), the lockstep and every packed-decode capacity,
and the KV splice of every prompt length the run can offer. A larger
admit tuple that first appears in the window compiles there, and
``compiles_in_window`` counts it.

The window then drives ``Engine.submit`` and ``Engine.round`` and stamps
every emitted token with the host clock when its round returns (the
engine has copied it to the host by then). Every request goes to the
engine the moment it is due, and the engine admits as it does by
default: every queued request a free slot, cost-ordered, in one packed
prefill. Open loop: a request is due at its arrival and its time to
first token counts from then. Closed loop: each client sends its next
request when the last one completes, and the window opens once every slot
is busy and some requests have finished.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import itertools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import generate as G
from bench.harness import spec as SP
from bench.harness import weights as WT


@dataclasses.dataclass
class Served:
    item: G.Item
    due_at: float                       # host clock
    req: object = None                  # the engine's Request
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Round:
    t0: float
    t1: float
    admitted: List[int]                 # prompt lengths prefilled
    kv_lens: List[int]                  # valid KV of each decoded slot
    packed: bool                        # decode took the packed path
    # the change of each counter of the engine's registry over the round,
    # where it changed (names as ``Registry.snapshot`` gives them)
    counters: Dict[str, float]


def model_config(c: dict):
    """The program's configuration object for a configuration file."""
    return SP.load_family(c).program_config(c)


def counter_change(before: dict, after: dict) -> Dict[str, float]:
    """Counters that moved between two ``Registry.snapshot()["counters"]``
    readings, by how much."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def make_params(cfg, seed: int):
    from repro.models import model as MD

    abstract = jax.eval_shape(lambda k: MD.init_params(k, cfg),
                              jax.random.key(0))
    return WT.make_params(seed, abstract)


class CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent
    cache) while ``active`` — a new program in the window is a compile
    the set-up missed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1


class ServeCell:
    """One serving run: ``setup()``, ``window()``, then ``served()``."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, *, tracer=None, base=SP.BENCH_DIR):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = seed, seconds
        self.tracer = tracer            # (start, stop) callables or None
        self.family = SP.load_family(config, base)
        self.cfg = self.family.program_config(config)
        self.items = G.serve_items(traffic, seed, seconds,
                                   config["vocab_size"])
        self.served: Dict[int, Served] = {}
        self.rounds: List[Round] = []
        self.compiles = CompileCounter()
        self.late_s = 0.0   # how far past a due time an idle wait woke

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        from repro.serve.engine import Engine

        self.params = make_params(self.cfg, self.seed)
        e = self.traffic["engine"]
        self.engine = Engine(
            self.params, self.cfg, slots=e["slots"], max_len=e["max_len"],
            prefill_block=e["prefill_block"],
            prefill_bucket=e["prefill_bucket"],
            decode_block=e["decode_block"], seed=self.seed)
        self._warm()

    def _drain(self, items, per_round: int):
        """Serve ``items`` to completion, handing the engine ``per_round``
        of them before each round."""
        pending = collections.deque(items)
        while pending or not self.engine.idle():
            for _ in range(min(per_round, len(pending))):
                it = pending.popleft()
                self.engine.submit(it.prompt, max_new=it.max_new,
                                   uid=-1 - it.uid)
            self.engine.round()

    def _warm(self):
        from repro.serve import decode as D
        from repro.serve import kv_cache as KV

        eng, e = self.engine, self.traffic["engine"]
        rng = G.run_rng(self.seed, 9)
        lens = G.distinct_prompt_lens(self.traffic, self.seconds)
        quantum = max(e["prefill_block"], e["prefill_bucket"])
        # a prompt of each padded length
        rep = {}
        for n in lens:
            rep.setdefault(-(-n // quantum) * quantum, n)
        pads = sorted(rep)
        vocab = self.config["vocab_size"]
        mk = lambda uid, n, new: G.Item(uid, 0.0, rng.integers(
            0, vocab, size=n, dtype=np.int32), new)
        # the packed prefill of every admit tuple of up to warm_admits
        # prompts: the engine puts the oldest first and alternates the
        # lightest and heaviest of the rest, so the head and the multiset
        # of the rest fix the tuple
        tuples = [admit_order(head, rest)
                  for k in range(1, self.traffic["warm_admits"] + 1)
                  for head in pads
                  for rest in itertools.combinations_with_replacement(
                      pads, k - 1)]
        uid = 0
        for t in tuples:
            self._drain([mk(uid + j, rep[p], 2) for j, p in enumerate(t)],
                        per_round=len(t))
            uid += len(t)
        # the lockstep decode: one request a round, each long enough that
        # about half the slots are live together, all with the same short
        # prompt, so the engine's auto mode takes lockstep there (a
        # lockstep compile inside a window was the miss without it)
        self._drain([mk(uid + i, min(lens), eng.B // 2 + 1)
                     for i in range(eng.B)], per_round=1)
        # the KV splice of every prompt length at every place in every
        # warmed tuple (its eager programs are keyed by the packed length,
        # the start and the length); results are dropped, the cache is
        # untouched
        for t in tuples:
            dummy = jax.tree.map(
                lambda c: jnp.zeros((c.shape[0], 1, sum(t)) + c.shape[3:],
                                    c.dtype), eng.cache)
            for start, p in zip(np.cumsum((0,) + t[:-1]), t):
                for n in lens:
                    if -(-n // quantum) * quantum == p:
                        jax.block_until_ready(KV.splice_slot(
                            eng.cache, 0, dummy, int(start), n))
            del dummy
        # every packed-decode capacity a round can ask for
        top = D.round_capacity(eng.B * -(-eng.s_cache // eng.decode_block))
        cap = 8
        while cap <= top:
            logits, _, _ = D.decode_step_packed(
                eng.params, self.cfg, eng.cache, eng.last_tok, eng.pos,
                [1], [0], block=eng.decode_block, impl=eng.decode_impl,
                capacity=cap)
            jax.block_until_ready(logits)
            cap *= 2
        gc.collect()

    # -- the window -----------------------------------------------------------
    def _round(self, live: List[Served]):
        """One engine round; stamps the tokens it emitted."""
        eng = self.engine
        before = eng.registry.snapshot()["counters"]
        counts = [len(s.req.out) for s in live]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round"):
            eng.round()
        t1 = time.perf_counter()
        counters = counter_change(before, eng.registry.snapshot()["counters"])
        admitted, kv = [], []
        for s, k in zip(live, counts):
            new = len(s.req.out) - k
            if new > 0:
                s.times.extend([t1] * new)
                kv.append(len(s.item.prompt) + k)
                if k == 0:
                    admitted.append(len(s.item.prompt))
        self.rounds.append(Round(
            t0, t1, admitted, kv,
            counters.get("decode_packed_launches", 0) > 0, counters))
        return t1

    def _submit(self, s: Served):
        self.engine.submit(s.item.prompt, max_new=s.item.max_new,
                           uid=s.item.uid)
        s.req = self.engine.queue[-1]
        self.served[s.item.uid] = s

    def window(self):
        """Run warm traffic and the measured window. Returns the host-clock
        bounds (t_start, t_end) of the window."""
        if self.traffic["loop"] == "open":
            return self._open_loop()
        return self._closed_loop()

    def _trace_at(self, now: float, t_end: float, state: dict):
        if self.tracer is not None and not state.get("on") and \
                now >= t_end - self.traffic["trace_s"]:
            self.tracer[0]()
            state["on"] = True

    def _open_loop(self):
        warm = self.traffic.get("warmup_s", 0.0)
        clock0 = time.perf_counter()
        t_start, t_end = clock0 + warm, clock0 + warm + self.seconds
        items = collections.deque(self.items)
        live: List[Served] = []
        trace_state: dict = {}
        now = clock0
        while now < t_end:
            if now >= t_start and not self.compiles.active:
                self.compiles.active = True
            while items and clock0 + items[0].due <= now:
                it = items.popleft()
                s = Served(it, clock0 + it.due)
                self._submit(s)
                live.append(s)
            if self.engine.idle():
                nxt = min(clock0 + items[0].due if items else t_end, t_end)
                time.sleep(max(0.0, nxt - now))
                now = time.perf_counter()
                self.late_s = max(self.late_s, now - nxt)
                continue
            self._trace_at(now, t_end, trace_state)
            now = self._round(live)
            live = [s for s in live if not s.req.done]
        self.compiles.active = False
        if trace_state.get("on"):
            self.tracer[1]()
        self.clock0 = clock0
        # due while the last round ran, never handed to the engine
        self.unsent_items = [it for it in items if clock0 + it.due < now]
        return t_start, now

    def _closed_loop(self):
        clients = self.traffic["clients"]
        items = collections.deque(G.residual_lengths(
            self.items, clients, self.seed))
        now = time.perf_counter()
        live: List[Served] = []
        for _ in range(clients):
            s = Served(items.popleft(), now)
            self._submit(s)
            live.append(s)
        t_start = t_end = None
        finished = 0
        trace_state: dict = {}
        while t_end is None or now < t_end:
            if t_end is not None:
                self._trace_at(now, t_end, trace_state)
            now = self._round(live)
            done = [s for s in live if s.req.done]
            live = [s for s in live if not s.req.done]
            finished += len(done)
            for _ in done:
                if not items:  # the pool again, under new ids
                    base = max(self.served) + 1
                    items.extend(dataclasses.replace(it, uid=base + i)
                                 for i, it in enumerate(self.items))
                s = Served(items.popleft(), now)
                self._submit(s)
                live.append(s)
            if t_end is None and finished >= self.traffic[
                    "open_after_finished"] and all(
                    r is not None for r in self.engine.slot_req):
                t_start, t_end = now, now + self.seconds
                self.warmup_s = now - self.rounds[0].t0
                self.compiles.active = True
        self.compiles.active = False
        if trace_state.get("on"):
            self.tracer[1]()
        self.clock0 = self.rounds[0].t0
        self.unsent_items = []
        return t_start, now

    # -- after the window -----------------------------------------------------
    def release(self):
        """Free the program's device state (before the reference runs)."""
        self.engine = None
        self.params = None
        gc.collect()

    def finished(self) -> List[Served]:
        return [s for s in self.served.values()
                if s.req.done and s.req.status == "done" and s.req.out]


def admit_order(head: int, rest) -> tuple:
    """The padded lengths of an admit round in the order the engine packs
    them: the oldest request first, then the lightest and the heaviest of
    the rest in turn."""
    rest = sorted(rest)
    out = [head]
    while rest:
        out.append(rest.pop(0) if len(out) % 2 else rest.pop())
    return tuple(out)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def serve_cell(cell, config, traffic, limits, seed, seconds, trace,
               t_process, chips, base, tracer=None, log=print):
    """One run of a serving cell: the run record for bench/run.py."""
    from bench.harness import checks as CK
    from bench.harness import device as DV

    sc = ServeCell(config, traffic, seed, seconds,
                   tracer=(tracer.start, tracer.stop) if tracer else None,
                   base=base)
    sc.setup()
    t_start, t_end = sc.window()
    setup_s = t_start - t_process
    dev = DV.device_block(chips)
    span = t_end - t_start

    in_win = lambda t: t_start <= t <= t_end
    gaps, tokens = [], 0
    for s in sc.served.values():
        ts = [t for t in s.times if in_win(t)]
        tokens += len(ts)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    due = [s for s in sc.served.values() if t_start <= s.due_at < t_end]
    ttft = [((s.times[0] if s.times and s.times[0] <= t_end else t_end)
             - s.due_at) for s in due]
    ttft += [t_end - (sc.clock0 + it.due) for it in sc.unsent_items
             if t_start <= sc.clock0 + it.due < t_end]
    attempted = len(ttft) if traffic["loop"] == "open" else len(due)
    failed = sum(s.req.done and s.req.status != "done" for s in due)
    values = {"setup_s": setup_s,
              "serve_tokens_per_s": tokens / span if span > 0 else 0.0}
    if gaps:
        values["itl_p95_ms"] = percentile(gaps, 95) * 1e3
        log(f"itl: {len(gaps)} gaps, median {percentile(gaps, 50) * 1e3:.3f}"
            f" ms, p95 {values['itl_p95_ms']:.3f} ms")
    if traffic["loop"] == "open" and ttft:
        values["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
        log(f"ttft: {len(ttft)} requests due, median "
            f"{percentile(ttft, 50) * 1e3:.3f} ms, p95 "
            f"{values['ttft_p95_ms']:.3f} ms; {len(ttft) - len(due)} due "
            f"never reached the engine; generator late by at most "
            f"{sc.late_s * 1e3:.3f} ms")
    rounds = [r for r in sc.rounds if t_start <= r.t0 < t_end]
    log(f"window {span:.3f} s: {len(rounds)} rounds, {tokens} tokens, "
        f"{sum(len(r.admitted) for r in rounds)} admits, "
        f"{sum(r.packed for r in rounds)} packed decode rounds, "
        f"{sc.compiles.count} programs built in the window; set-up "
        f"{setup_s:.3f} s" + (f" (closed-loop warm-up {sc.warmup_s:.3f} s)"
                              if traffic["loop"] == "closed" else ""))
    rec = {"shape": sc.family.Shape.from_config(config), "rounds": rounds,
           "compiles": sc.compiles.count, "trace": None}
    if tracer is not None:
        rec["traced_rounds"] = [r for r in rounds
                                if r.t0 >= tracer.started_at]
        rec["trace"] = tracer.reduce("bench.round")
    finished = sc.finished()
    sc.release()
    gap, detail = CK.served_gap(config, traffic, seed, finished, base=base)
    log(f"reference: {detail}")
    return {"values": values, "rec": rec, "attempted": attempted,
            "failed": failed, "device": dev,
            "compared": {"served_gap_max": gap}}
