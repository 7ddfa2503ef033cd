"""Training cells: the program's packed train step, AdamW, one packed row.

Set-up makes the weights on the device from the seed, compiles the
program's train step once (``train_step.make_train_step`` over the
traffic file's document layout, jitted with the state donated, as
``launch/train.py`` runs it), builds the window's batches ahead of time,
and drives that same compiled step from the seed through its first
``check_steps`` steps, reading what the correctness check compares:

  loss of each step                    the step's own metrics
  norm of the first clipped gradient   each leaf of AdamW's first moment
                                       after step 1, over (1 - b1)
  norm of the parameters' change       each leaf after the last check
                                       step, against the seeded weights

The window then keeps stepping the same state, with at most ``inflight``
steps queued on the device, and counts the real (unpadded) tokens of the
steps it completes. The plain reference (reference.train_reference)
follows the check steps once the program's state is freed.
"""

from __future__ import annotations

import collections
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import generate as G
from bench.harness import reference as REF
from bench.harness import spec as SP
from bench.harness import weights as WT


def _key(path: str, layer=None) -> str:
    return path if layer is None else f"{path}@{layer}"


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Per-leaf float32 norms on the device, a layer leaf split into its
    layers: keys as reference.flat_params has them."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        p = WT._path_str(path)
        x = x.astype(jnp.float32)
        if p.startswith("layers/"):
            norms = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(
                range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[_key(p, i)] = norms[i]
        else:
            out[p] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


@jax.jit
def _moment_norms(m, b1):
    return leaf_norms(jax.tree.map(lambda x: x / (1.0 - b1), m))


@jax.jit
def _change_norms(params, params0):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, params0))


class TrainCell:
    """One training run: ``setup()``, ``window()``, then the readings."""

    def __init__(self, config: dict, traffic: dict, seed: int, *,
                 tracer=None, feed=None, base=SP.BENCH_DIR):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.tracer = tracer            # (start, stop) callables or None
        # the batch of a step; the correctness tests swap in a broken one
        self.feed = feed or (lambda step: G.train_batch(
            traffic, config["vocab_size"], seed, step))
        self.family = SP.load_family(config, base)
        train_reference_of(self.family, config)
        self.cfg = self.family.program_config(config)
        self.opt_cfg = traffic["optimizer"]
        self.real_tokens = int(sum(traffic["doc_lens"]))
        self.readings: dict = {}

    # -- set-up ---------------------------------------------------------------
    def setup(self):
        from repro.kernels.tri_attn import ops as OPS
        from repro.models import model as MD
        from repro.train import optimizer as OPT
        from repro.train import train_step as TS

        block = self.traffic["block"]
        o = self.opt_cfg
        self.opt = OPT.OptConfig(
            lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
            warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
            min_lr_frac=o["min_lr_frac"])
        pads = [-(-n // block) * block for n in self.traffic["doc_lens"]]
        psched = OPS.make_packed_sched(pads, block=block,
                                       window=self.cfg.sliding_window)
        step = TS.make_train_step(self.cfg, self.opt,
                                  attn_impl=OPS.platform_impl(),
                                  packed=psched, block=block)
        self.step_fn = jax.jit(step, donate_argnums=(0,))
        self.abstract = jax.eval_shape(
            lambda k: MD.init_params(k, self.cfg), jax.random.key(0))
        params = WT.make_params(self.seed, self.abstract)
        opt_state = jax.jit(lambda p: OPT.init_opt_state(self.opt, p))(
            params)
        self.state = TS.TrainState(params=params, opt_state=opt_state,
                                   step=jnp.zeros((), jnp.int32))
        keys = ("tokens", "labels", "mask", "positions")
        n = self.traffic["check_steps"] + self.traffic["batches"]
        self.batches = [{k: jnp.asarray(b[k]) for k in keys}
                        for b in map(self.feed, range(n))]
        self._check_steps()

    def _check_steps(self):
        """The first steps, through the window's own call and feed."""
        losses = []
        for i in range(self.traffic["check_steps"]):
            self.state, met = self.step_fn(self.state, self.batches[i])
            losses.append(met["loss"])
            if i == 0:
                self.readings["grad"] = jax.device_get(_moment_norms(
                    self.state.opt_state["m"], self.opt.b1))
        params0 = WT.make_params(self.seed, self.abstract)
        self.readings["change"] = jax.device_get(_change_norms(
            self.state.params, params0))
        del params0
        self.readings["loss"] = [float(x) for x in jax.device_get(losses)]
        gc.collect()

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float):
        """Step until the window's time is up. Returns (t_start, t_end,
        steps completed, metrics of each step)."""
        inflight = self.traffic.get("inflight", 2)
        first = self.traffic["check_steps"]
        pool = self.batches[first:]
        mets: List[dict] = []
        queue = collections.deque()
        t_start = time.perf_counter()
        t_end = t_start + seconds
        trace_at = t_end - self.traffic["trace_s"]
        traced = None
        i = 0
        now = t_start
        while now < t_end:
            if self.tracer is not None and traced is None and \
                    now >= trace_at:
                jax.block_until_ready([m["loss"] for m in queue])
                queue.clear()
                self.tracer[0]()
                traced = {"from_step": i,
                          "span": jax.profiler.TraceAnnotation(
                              "bench.window")}
                traced["span"].__enter__()
            self.state, met = self.step_fn(self.state, pool[i % len(pool)])
            mets.append(met)
            queue.append(met)
            i += 1
            if len(queue) > inflight:
                jax.block_until_ready(queue.popleft()["loss"])
                now = time.perf_counter()
        jax.block_until_ready(self.state)
        t_stop = time.perf_counter()
        if traced is not None:
            traced["span"].__exit__(None, None, None)
            self.tracer[1]()
        self.traced_steps = (i - traced["from_step"]) if traced else 0
        return t_start, t_stop, i, mets

    def release(self):
        self.state = None
        self.batches = None
        gc.collect()


def check_batches(traffic: dict, config: dict, seed: int, feed=None
                  ) -> List[dict]:
    """What the reference follows: the check steps' documents."""
    feed = feed or (lambda step: G.train_batch(
        traffic, config["vocab_size"], seed, step))
    out = []
    for step in range(traffic["check_steps"]):
        b = feed(step)
        out.append({"tokens": b["real_tokens"], "labels": b["real_labels"],
                    "docs": traffic["doc_lens"]})
    return out


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None
             ) -> tuple:
    """Worst leaf of |program norm - reference norm| over the larger of
    that leaf's reference norm and the median leaf's. Returns (gap, leaf)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(float(prog[k]) - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def compare(readings: dict, ref: dict, floor: float = 1e-3) -> dict:
    """The numbers ``correct`` compares, with what they came from. Leaves
    whose reference gradient is under ``floor`` of the median leaf's move
    by round-off alone and are left out of the change.

    Each step's loss is reported (``loss_gap_max``, the worst relative gap
    over the steps) but not compared: on the chip a step over half the
    batch reads under ten times what sound runs read (PERF.md), so no
    limit could hold it; the gradient and the change catch it, and the
    gradient catches the control."""
    med = float(np.median(list(ref["grad"].values())))
    moved = {k for k, v in ref["grad"].items() if v >= floor * med}
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(readings["loss"], ref["loss"]))
    grad_gap, grad_leaf = norm_gap(readings["grad"], ref["grad"])
    change_gap, change_leaf = norm_gap(readings["change"], ref["change"],
                                       moved)
    return {"values": {"grad_norm_gap_max": grad_gap,
                       "change_norm_gap_max": change_gap},
            "detail": {"loss_gap_max": loss_gap,
                       "program_loss": readings["loss"],
                       "reference_loss": ref["loss"],
                       "grad_leaf": grad_leaf, "change_leaf": change_leaf,
                       "left_out": sorted(set(ref["grad"]) - moved)}}


def train_reference_of(family, config: dict):
    """The family's reference model of ``config``, which has to train."""
    m = family.Reference.from_config(config)
    if not callable(getattr(m, "train_block", None)):
        raise SP.SpecError(f"family {config.get('family', 'dense')!r} of "
                           f"configuration {config.get('name')!r} has no "
                           f"train_block: its reference cannot train")
    return m


def reference_readings(config: dict, traffic: dict, seed: int, prec="f32",
                       feed=None, base=SP.BENCH_DIR) -> dict:
    m = train_reference_of(SP.load_family(config, base), config)
    return REF.train_reference(m, seed, check_batches(traffic, config, seed,
                                                      feed),
                               traffic["optimizer"], prec=prec)


def train_cell(cell, config, traffic, limits, seed, seconds, trace,
               t_process, chips, base, tracer=None, log=print):
    """One run of a training cell: the run record for bench/run.py."""
    from bench.harness import device as DV

    tc = TrainCell(config, traffic, seed,
                   tracer=(tracer.start, tracer.stop) if tracer else None,
                   base=base)
    tc.setup()
    t_start, t_stop, steps, mets = tc.window(seconds)
    setup_s = t_start - t_process
    span = t_stop - t_start
    dev = DV.device_block(chips)
    host = jax.device_get([(m["loss"], m["grad_norm"]) for m in mets])
    failed = sum(not (np.isfinite(a) and np.isfinite(b)) for a, b in host)
    values = {"setup_s": setup_s,
              "train_tokens_per_s": tc.real_tokens * steps / span}
    log(f"window {span:.3f} s: {steps} steps of {tc.real_tokens} real "
        f"tokens ({sum(-(-n // traffic['block']) * traffic['block'] for n in traffic['doc_lens'])} "
        f"packed), {values['train_tokens_per_s']:.1f} tokens/s, "
        f"{span / steps * 1e3:.3f} ms a step; set-up {setup_s:.3f} s")
    rec = {"shape": tc.family.Shape.from_config(config), "trace": None,
           "traced_steps": [traffic["doc_lens"]] * tc.traced_steps}
    readings = tc.readings
    tc.release()
    if tracer is not None:
        rec["trace"] = tracer.reduce("bench.window")
    ref = reference_readings(config, traffic, seed, base=base)
    cmp = compare(readings, ref)
    log(f"reference: {cmp['detail']}")
    return {"values": values, "rec": rec, "attempted": steps,
            "failed": int(failed), "device": dev,
            "compared": cmp["values"]}
