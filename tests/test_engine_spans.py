"""The engine's round spans: every host phase of Engine.round() runs under
a span of serve/engine.SPANS, every blocking device-to-host read under an
engine.to_host span inside one of them, and the spans change no token.
The profiler-trace reduction keeps its old readings when the phase spans
nest inside the harness's round span."""

import json
import pathlib
import re

import jax
import numpy as np
import pytest

from bench.harness import spec as SP
from bench.harness import trace_reduce as TRC
from repro.configs import registry as REG
from repro.models import model as MD
from repro.obs import schema as SCH
from repro.obs import sinks as SK
from repro.obs import trace as TR
from repro.serve import engine as E

PHASES = {"engine.schedule", "engine.decode_plan", "engine.admit_commit",
          "engine.guard", "engine.sample", "engine.commit"}
DECODE = ["engine.schedule", "engine.decode_plan", "engine.decode_round",
          "engine.guard", "engine.sample", "engine.commit"]
ROUND_ORDERS = {
    "split": [["engine.schedule", "engine.admit_batch", "engine.admit_commit"]
              + DECODE[1:], DECODE],
    "fused": [["engine.schedule", "engine.decode_plan", "engine.fused_step",
               "engine.admit_commit", "engine.guard", "engine.sample",
               "engine.commit"], DECODE],
}
READS = {"logits", "tokens", "admit_tokens", "last_tok", "states_finite"}
PROMPTS = [np.array([3, 1, 4, 1, 5], np.int32),
           np.array([2, 7, 1], np.int32),
           np.array([9, 9, 8, 2, 6, 5], np.int32)]


@pytest.fixture(scope="module")
def model():
    cfg = REG.smoke_config("yi-9b")
    return cfg, MD.init_params(jax.random.key(0), cfg)


def _serve(model, mode, slots=2, prompts=PROMPTS):
    cfg, params = model
    eng = E.Engine(params, cfg, slots=slots, max_len=48, temperature=0.0,
                   step_mode=mode)
    for uid, p in enumerate(prompts):
        eng.submit(p, max_new=6, uid=uid)
    return eng.run()


def _traced_run(model, mode, trace_dir, **kw):
    """(tokens, span events) of a run with the trace sink on."""
    path = SK.enable(trace_dir=str(trace_dir), metrics_path=None,
                     run_id="spans")
    try:
        out = _serve(model, mode, **kw)
    finally:
        SK.disable()
    events = [json.loads(line) for line in
              pathlib.Path(path).read_text().splitlines()]
    return out, [ev for ev in events if ev["type"] == "span"]


@pytest.fixture(scope="module", params=["split", "fused"])
def traced(request, model, tmp_path_factory):
    """(mode, tokens, span events) of a run with the trace sink on."""
    out, spans = _traced_run(model, request.param,
                             tmp_path_factory.mktemp("trace"))
    return request.param, out, spans


def _rounds(spans):
    """Each engine.round event with the events that closed inside it
    (spans close innermost first, so they precede their round)."""
    out, inside = [], []
    for ev in spans:
        if ev["name"] == "engine.round":
            out.append((ev, inside))
            inside = []
        else:
            inside.append(ev)
    assert not inside, "spans outside any engine.round"
    return out


def test_each_round_runs_its_phases_in_order(traced):
    mode, _, spans = traced
    rounds = _rounds(spans)
    assert len(rounds) >= 4
    seen = set()
    for rnd, inside in rounds:
        assert rnd["depth"] == 0
        children = [ev["name"] for ev in inside
                    if ev["parent"] == "engine.round"]
        assert children in ROUND_ORDERS[mode], children
        seen.add(tuple(children))
        for ev in inside:
            assert rnd["t0_ns"] <= ev["t0_ns"] <= ev["t1_ns"] \
                <= rnd["t1_ns"], ev
    # both kinds of round ran: with admits and decode-only
    assert len(seen) == 2


def test_host_reads_are_spans_inside_a_phase(traced):
    _, _, spans = traced
    for _, inside in _rounds(spans):
        reads = [ev for ev in inside if ev["name"] == "engine.to_host"]
        assert reads
        for ev in reads:
            assert ev["parent"] in PHASES, ev
            assert ev["attrs"]["nbytes"] > 0
            assert ev["attrs"]["what"] in READS, ev


@pytest.mark.parametrize("slots", [2, 4])
@pytest.mark.parametrize("mode", ["split", "fused"])
def test_a_decode_round_reads_pos_logits_tokens_and_each_slot(
        model, mode, slots, tmp_path):
    """The first decode-only round with every slot live and tokens to go
    makes two blocking reads, the logits and the tokens, whatever the
    slot count: the host owns the slot positions, so neither ``pos`` nor
    any slot's position is read back."""
    prompts = (PROMPTS * 2)[:slots]
    _, spans = _traced_run(model, mode, tmp_path, slots=slots,
                           prompts=prompts)
    inside = next(i for _, i in _rounds(spans)
                  if [e["name"] for e in i
                      if e["parent"] == "engine.round"] == DECODE
                  and next(e for e in i if e["name"] ==
                           "engine.decode_round")["attrs"]["live"] == slots)
    reads = [ev["attrs"]["what"] for ev in inside
             if ev["name"] == "engine.to_host"]
    assert reads == ["logits", "tokens"]


def test_span_events_validate(traced):
    _, _, spans = traced
    for ev in spans:
        assert SCH.validate_event(ev) == [], ev
        assert ev["name"] in E.SPANS


def test_tokens_do_not_depend_on_the_trace_sink(traced, model):
    mode, out, _ = traced
    assert _serve(model, mode) == out
    assert set(out) == {0, 1, 2}


@pytest.mark.parametrize("mode", ["split", "fused"])
def test_device_values_reach_the_host_only_in_to_host(model, mode,
                                                      monkeypatch):
    """Every conversion of a device array to a host value during the
    rounds happens inside an engine.to_host span (on the CPU numpy reads
    a device array through its buffer, so the scalar reads are what this
    sees; on a TPU a transfer guard sees them all)."""
    cls = type(jax.numpy.zeros(()))
    seen = []

    def watch(name):
        orig = getattr(cls, name)

        def read(self, *args, **kw):
            span = TR.current_span()
            seen.append((name, span.name if span else None))
            return orig(self, *args, **kw)
        monkeypatch.setattr(cls, name, read)

    for name in ("__array__", "__int__", "__bool__", "__index__",
                 "__float__", "item", "tolist"):
        watch(name)
    out = _serve(model, mode)
    monkeypatch.undo()
    assert set(out) == {0, 1, 2}
    # the scalar reads left in a round are the states guard's
    assert {n for n, _ in seen} == {"__bool__"}
    assert {span for _, span in seen} == {"engine.to_host"}


def test_span_vocabulary_covers_every_span_the_engine_opens():
    """A span renamed in the engine fails here: every name the engine
    source opens is in SPANS, and every engine span that the profiler
    reduction reads is still opened."""
    src = pathlib.Path(E.__file__).read_text()
    opened = set(re.findall(r'TR\.span\(\s*"([^"]+)"', src))
    assert opened == set(E.SPANS)
    read_by_bench = {n for n in TRC.SPANS if n.startswith("engine.")}
    assert read_by_bench <= set(E.SPANS)


def _reduced(spans):
    """A 10 us window of two rounds, each with device work under
    engine.decode_round and host work before and after it."""
    ops = [TRC.Op("%a = f32[] fusion(...)", 1_000, 3_000),
           TRC.Op("%b = f32[] fusion(...)", 6_000, 8_000)]
    return TRC.reduce(TRC.Trace(ops=ops, spans=spans, devices=1), {},
                      0, 10_000)


def test_reduction_with_phase_spans_keeps_the_old_readings():
    """Idle gaps are labelled by the innermost span open at their middle:
    with the phase spans, the idle that bench.round held falls under
    engine.* labels; busy time and busy time per span do not move."""
    harness = [("bench.round", 0, 5_000), ("bench.round", 5_000, 10_000),
               ("engine.decode_round", 600, 3_000),
               ("engine.decode_round", 5_600, 8_000)]
    phases = [("engine.round", 100, 4_900), ("engine.round", 5_100, 9_900),
              ("engine.schedule", 100, 600),
              ("engine.schedule", 5_100, 5_600),
              ("engine.commit", 3_000, 4_800),
              ("engine.to_host", 3_200, 4_000),
              ("engine.commit", 8_000, 9_800),
              ("engine.to_host", 8_200, 9_400)]
    old, new = _reduced(harness), _reduced(harness + phases)
    for key in ("window_ns", "busy_ns", "kernel_ns", "top_ops"):
        assert new[key] == old[key]
    assert new["busy_ns"] == 4_000
    for name in ("bench.round", "engine.decode_round"):
        assert new["busy_in_span_ns"][name] == old["busy_in_span_ns"][name]
    assert new["busy_in_span_ns"]["engine.decode_round"] == 4_000
    assert old["idle_by_label"] == {"bench.round": 6_000}
    assert new["idle_by_label"] == {"engine.schedule": 1_000,
                                    "engine.commit": 3_000,
                                    "engine.to_host": 2_000}
    assert new["span_count"]["engine.round"] == 2
    assert new["span_count"]["engine.to_host"] == 2


def test_recorded_trace_keeps_its_readings_under_phase_spans():
    """The v5e trace of bench/tests/data with an engine.round span inside
    each bench.round: busy time, kernel time and busy time per old span
    name do not move."""
    small = TRC.load(pathlib.Path(TRC.__file__).parents[1] / "tests" /
                     "data" / "trace_small.xplane.pb")
    rounds = [(a, b) for n, a, b in small.spans if n == "bench.round"]
    lo, hi = min(a for a, _ in rounds), max(b for _, b in rounds)
    fam = SP.kernel_families()
    old = TRC.reduce(small, fam, lo, hi)
    small.spans += [("engine.round", a + 1, b - 1) for a, b in rounds]
    new = TRC.reduce(small, fam, lo, hi)
    assert new["busy_ns"] == old["busy_ns"]
    assert new["kernel_ns"] == old["kernel_ns"]
    for name, ns in old["busy_in_span_ns"].items():
        assert new["busy_in_span_ns"][name] == ns
    assert sum(new["idle_by_label"].values()) == \
        sum(old["idle_by_label"].values())
    assert new["idle_by_label"]["engine.round"] > 0
