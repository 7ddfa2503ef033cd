"""Drive the main serving and training path once on one TPU v5e.

  python chip_smoke.py               # one chip: kernels, serving, training
  python chip_smoke.py --four-chips  # the sharded train step on a 2x2 mesh,
                                     # compared with the same step on one chip

Everything runs in this one process (a chip belongs to one process). Off a
TPU it exits non-zero before doing anything. Phases:

  kernels   every Pallas kernel of the main path, compiled, at yi-9b head
            geometry (32 query / 4 KV heads, head_dim 128, bf16) against
            its ``ref`` implementation on the chip;
  serving   yi-9b at its published widths cut to SERVE_LAYERS layers,
            through launch/serve.py's Engine, 8 requests of 200-1500 prompt
            tokens and 32 new tokens each, step_mode split and fused; the
            engine's own prefill / decode / fused-step functions are then
            run again on its state with ``impl="ref"`` and the logits
            compared;
  training  TRAIN_STEPS packed-document train steps of the same widths cut
            to TRAIN_LAYERS layers (AdamW).

Every phase prints its compile and run seconds and the device's
peak_bytes_in_use; a failed check raises, so the script never exits 0 with
a failed phase. The last line of stdout is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "yi-9b"
BLOCK = 128
SERVE_LAYERS = 16   # of 48: ~6.6 GB of bf16 weights, KV cache beside them
# AdamW keeps bf16 weights plus f32 moments: 10 bytes a parameter, and the
# 524M-parameter embedding and head are always there. A v5e compile of the
# packed train step puts 2 layers at 12.4 GB peak (3 layers would be ~14.4
# of the 16 GB), so training keeps 2 layers.
TRAIN_LAYERS = 2
TRAIN_STEPS = 3
TRAIN_DOCS = (1500, 1100, 700, 500, 300, 96)  # 4480 packed rows

# Tolerances. Kernel outputs are bf16: one bf16 ulp of the largest value is
# 2**-8 of it, and kernel and reference each round once, so 4 ulps (1.6e-2
# of the largest magnitude) bound a correct kernel; an f32->bf16 slip in the
# accumulation, or any wrong tile, is far above it. The packed backward
# sums 8 bf16 per-head dk/dv partials per KV head: 3e-2. EDM and the 3-body
# totals are f32 with d = 4 / 8 feature dims, where a bf16 matmul pass is
# the worst the MXU does: 1e-2 and 2e-2.
TOL = {"attn": 1.6e-2, "attn_grad": 3e-2, "edm": 1e-2, "3body": 2e-2}
# Serving logits: the model runs a bf16 residual stream through 16 layers,
# and the Pallas and reference attentions round their bf16 outputs
# differently in every layer; the relative L2 distance of the logits must
# stay under 5e-2 (random weights: the logits are O(1), a wrong mask or
# tile moves them by O(1)).
LOGIT_REL_L2 = 5e-2


def log(msg: str):
    print(msg, flush=True)


def memory_line(tag: str, device=None) -> str:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return (f"[{tag}] bytes_in_use={stats.get('bytes_in_use', 0)} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 0)}")


def rel_err(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def check(name: str, value: float, limit: float):
    ok = value <= limit
    log(f"  {name}: {value:.3e} (limit {limit:.1e}) {'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"{name}: {value:.3e} > {limit:.1e}")


def reset_counters():
    from repro.obs import metrics as MET

    MET.global_registry().reset()


def launches(expect):
    """Launch counters since the last reset: every kernel ran as Pallas,
    and each name in ``expect`` launched at least once."""
    from repro.obs import launch as OBS

    summary = OBS.kernel_summary()
    reset_counters()
    for name, d in sorted(summary.items()):
        log(f"  launches {name}: {d['launches']} impls={d['impls']} "
            f"tiles={d['tiles_launched']}")
        if d["impls"] != ["pallas"]:
            raise AssertionError(f"{name} ran as {d['impls']}, not Pallas")
    missing = [n for n in expect if summary.get(n, {}).get("launches", 0) < 1]
    if missing:
        raise AssertionError(f"no Pallas launch of {missing}")
    return summary


def timed(fn, *args):
    """Compile ``fn`` for ``args``, then run it once: (out, compile s,
    run s)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t1 - t0, time.perf_counter() - t1


def highest(fn):
    """The plain reference runs its float32 matmuls at full precision."""
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return run


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def phase_kernels(h=32, hkv=4, d=128, blk=BLOCK, slots=8, s_cache=2048,
                  seed=0):
    from repro.kernels.tri_3body import ops as O3
    from repro.kernels.tri_3body import ref as R3
    from repro.kernels.tri_attn import ops as OPS
    from repro.kernels.tri_edm import ops as OE
    from repro.serve import decode as D

    reset_counters()
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(seed), 16))
    rnd = lambda shape, dt=bf: jax.random.normal(next(keys), shape,
                                                 jnp.float32).astype(dt)
    t_phase = time.perf_counter()

    def kernel(name, tol, pallas_fn, ref_fn, *args):
        got, tc, tr = timed(pallas_fn, *args)
        want = highest(ref_fn)(*args)  # eager: some references use numpy
        log(f"  {name}: compile {tc:.2f}s run {tr:.4f}s")
        pairs = zip(got, want) if isinstance(got, tuple) else [(got, want)]
        for i, (g, w) in enumerate(pairs):
            check(f"{name}[{i}] max|err|/max|ref|", rel_err(g, w), tol)

    # packed prefill forward + backward: ltm, band and prefix members
    psched = OPS.make_packed_sched([2 * blk, 5 * blk, 3 * blk], block=blk,
                                   window=[None, 2 * blk + blk // 3, None],
                                   prefix=[0, 0, blk])
    s = psched.s_total
    q, k, v = rnd((1, h, s, d)), rnd((1, hkv, s, d)), rnd((1, hkv, s, d))
    do = rnd((1, h, s, d))
    attn = lambda impl: (lambda a, b, c: OPS.packed_prefill_attention(
        a, b, c, psched, impl=impl))
    kernel("packed_prefill_fwd", TOL["attn"], attn("pallas"), attn("ref"),
           q, k, v)

    def grad(impl):
        def g(a, b, c, dout):
            _, vjp = jax.vjp(attn(impl), a, b, c)
            return vjp(dout)
        return g
    kernel("packed_prefill_bwd", TOL["attn_grad"], grad("pallas"),
           grad("ref"), q, k, v, do)

    # causal LTM forward (the per-domain path)
    q1, k1, v1 = rnd((1, h, 16 * blk, d)), rnd((1, hkv, 16 * blk, d)), \
        rnd((1, hkv, 16 * blk, d))
    ltm = lambda impl: (lambda a, b, c: OPS.triangular_attention(
        a, b, c, impl=impl, block_q=blk, block_k=blk))
    kernel("ltm_fwd", TOL["attn"], ltm("pallas"), ltm("ref"), q1, k1, v1)

    # packed decode round: skewed live slots, two retired
    kv_lens = [1, s_cache // 16 + 2, s_cache // 3, s_cache,
               3 * s_cache // 4, s_cache // 8 + 1]
    live = [0, 1, 3, 4, 6, 7]
    tbl, needed = OPS.make_decode_table(kv_lens, live, blk=blk,
                                        n_members=slots + 1, n_slots=slots,
                                        s_cache=s_cache)
    qd = rnd((slots, h, d))
    kc, vc = rnd((slots, s_cache, hkv, d)), rnd((slots, s_cache, hkv, d))
    tbl = jnp.asarray(tbl)

    def decode(impl):
        spec = OPS.DecodeRoundSpec(n_members=slots + 1,
                                   capacity=D.round_capacity(needed),
                                   blk=blk, impl=impl)
        return lambda a, b, c, t: OPS.packed_decode_attention(a, b, c, t,
                                                              spec)
    kernel("packed_decode", TOL["attn"], decode("pallas"), decode("ref"),
           qd, kc, vc, tbl)

    # fused step: two admitted prompts beside four live decode slots
    ps2 = OPS.make_packed_sched([2 * blk, 3 * blk], block=blk)
    ftbl, fneeded = OPS.make_fused_table(ps2, kv_lens[:4], live[:4],
                                         blk=blk, n_members=2 + slots + 1,
                                         n_slots=slots, s_cache=s_cache)
    qp, kp, vp = rnd((1, h, ps2.s_total, d)), rnd((1, hkv, ps2.s_total, d)), \
        rnd((1, hkv, ps2.s_total, d))
    ftbl = jnp.asarray(ftbl)

    def fused(impl):
        spec = OPS.FusedStepSpec(
            n_members=2 + slots + 1,
            capacity=ps2.steps + D.round_capacity(fneeded - ps2.steps),
            blk=blk, impl=impl)
        return lambda a, b, c, e, f, g, t: OPS.fused_step_attention(
            a, b, c, e, f, g, t, ps2, spec)
    kernel("fused_step", TOL["attn"], fused("pallas"), fused("ref"),
           qp, kp, vp, qd, kc, vc, ftbl)

    # the paper's EDM (LTM grid) and the strict tetrahedral 3-body
    x = rnd((32 * blk, 4), jnp.float32)
    kernel("tri_edm_ltm", TOL["edm"],
           lambda a: OE.edm(a, blk, squared=True, impl="pallas"),
           lambda a: OE.pack_tri(OE.edm(a, blk, squared=True, impl="ref"),
                                 blk), x)
    pts = jax.random.uniform(next(keys), (8 * blk, 8), jnp.float32)
    kernel("tri_3body_strict", TOL["3body"],
           lambda a: O3.three_body_total(a, blk, impl="pallas", strict=True),
           R3.three_body_total_strict_ref, pts)
    launches(["tri_attn.packed_fwd", "tri_attn.packed_bwd_dq",
              "tri_attn.packed_bwd_dkv", "tri_attn.fwd",
              "tri_attn.packed_decode_fwd", "tri_attn.fused_step_fwd",
              "tri_edm.ltm", "tri_3body.tet"])
    log(f"  phase kernels: {time.perf_counter() - t_phase:.1f}s")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def logits_rel_l2(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def serve_argv(step_mode: str, layers=SERVE_LAYERS, slots=8, max_len=2048,
               prompts=(200, 1500), max_new=32, requests=8, arch=ARCH,
               full=True):
    argv = ["--arch", arch, "--layers", str(layers), "--slots", str(slots),
            "--max-len", str(max_len), "--min-prompt-len", str(prompts[0]),
            "--prompt-len", str(prompts[1]), "--max-new", str(max_new),
            "--requests", str(requests), "--step-mode", step_mode]
    return argv + (["--full-config"] if full else [])


def check_engine(engine, requests: int, max_new: int):
    st = engine.stats
    done = [r for r in engine.finished if r.status == "done"]
    log(f"  {len(done)}/{requests} done; degraded="
        f"{st['launches_degraded_total']} quarantined="
        f"{st['slots_quarantined_total']} fused_fallbacks="
        f"{st['fused_fallbacks']} failures={st['failures']}")
    if len(done) != requests or any(len(r.out) != max_new for r in done):
        raise AssertionError("not every request finished with its tokens")
    if st["launches_degraded_total"] or st["slots_quarantined_total"] \
            or st["fused_fallbacks"] or st["failures"]:
        # the engine's ladder would have hidden a failed kernel or a NaN
        raise AssertionError("the engine fell back or quarantined a slot")
    vocab = engine.cfg.vocab_size
    if any(not 0 <= t < vocab for r in done for t in r.out):
        raise AssertionError("a sampled token is outside the vocabulary")


def compare_prefill(engine):
    """Packed prefill logits at each prompt's last token: the engine's
    prefill function on Pallas against the same function on ``ref``."""
    from repro.models import model as MD
    from repro.serve import decode as D

    prompts = [r.prompt for r in sorted(engine.finished, key=lambda r: r.uid)]
    outs = {}
    for impl in ("pallas", "ref"):
        run = D.packed_prefill if impl == "pallas" else highest(
            D.packed_prefill)
        t0 = time.perf_counter()
        _, starts, lens, hidden, _ = run(
            engine.params, engine.cfg, prompts, block=engine.prefill_block,
            attn_impl=impl)
        rows = np.asarray([s + n - 1 for s, n in zip(starts, lens)])
        outs[impl] = np.asarray(MD.logits_from_hidden(
            engine.params, engine.cfg, hidden[:, rows])[0])
        log(f"  prefill[{impl}] {hidden.shape[1]} packed rows: "
            f"{time.perf_counter() - t0:.1f}s")
    check("prefill logits rel L2 (pallas vs ref)",
          logits_rel_l2(outs["pallas"], outs["ref"]), LOGIT_REL_L2)


def compare_decode_steps(engine):
    """On the engine's final cache: a packed decode round and a fused step
    (admits beside live decode rows), Pallas against ``ref``."""
    from repro.serve import decode as D

    b = engine.B
    live = list(range(b // 2, b))
    pos = np.asarray(engine.pos)
    kv_lens = [int(min(pos[s] + 1, engine.s_cache)) for s in live]
    prompts = [r.prompt for r in sorted(engine.finished,
                                        key=lambda r: r.uid)][:b // 2]
    dec, fus = {}, {}
    for impl in ("pallas", "ref"):
        wrap = (lambda f: f) if impl == "pallas" else highest
        logits, _, _ = wrap(D.decode_step_packed)(
            engine.params, engine.cfg, engine.cache, engine.last_tok,
            engine.pos, kv_lens, live, block=engine.decode_block, impl=impl)
        dec[impl] = np.asarray(logits[live, 0])
        adm, ldec, *_ = wrap(D.fused_step)(
            engine.params, engine.cfg, engine.cache, prompts,
            engine.last_tok, engine.pos, kv_lens, live,
            block=engine.decode_block, impl=impl)
        fus[impl] = np.concatenate([np.asarray(adm),
                                    np.asarray(ldec[np.asarray(live)])])
    check("packed decode logits rel L2 (pallas vs ref)",
          logits_rel_l2(dec["pallas"], dec["ref"]), LOGIT_REL_L2)
    check("fused step logits rel L2 (pallas vs ref)",
          logits_rel_l2(fus["pallas"], fus["ref"]), LOGIT_REL_L2)


def phase_serving(step_mode: str, argv=None, requests=8, max_new=32):
    from repro.launch import serve as SERVE

    reset_counters()
    t0 = time.perf_counter()
    engine = SERVE.main(argv or serve_argv(step_mode))
    t1 = time.perf_counter()
    check_engine(engine, requests, max_new)
    expect = (["tri_attn.packed_fwd"] if step_mode == "split"
              else ["tri_attn.fused_step_fwd"])
    launches(expect)
    # the same requests again on the warm engine: run time without compiles
    finished = sorted(engine.finished, key=lambda r: r.uid)
    engine.finished = []
    for r in finished:
        engine.submit(r.prompt, max_new=max_new, uid=r.uid)
    t2 = time.perf_counter()
    engine.run()
    t3 = time.perf_counter()
    check_engine(engine, requests, max_new)
    again = {r.uid: r.out for r in engine.finished}
    same = sum(again[r.uid] == r.out for r in finished)
    log(f"  serving[{step_mode}]: first run {t1 - t0:.1f}s (weights, "
        f"compiles), warm run {t3 - t2:.1f}s; {same}/{len(finished)} "
        f"greedy streams repeat token for token")
    compare_prefill(engine) if step_mode == "split" \
        else compare_decode_steps(engine)
    del engine
    gc.collect()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def train_setup(layers=TRAIN_LAYERS, docs=TRAIN_DOCS, block=BLOCK,
                arch=ARCH, full=True, rows=1):
    from repro.configs import registry as REG
    from repro.kernels.tri_attn import ops as OPS
    from repro.train import data as DATA
    from repro.train import optimizer as OPT
    from repro.train import train_step as TS

    cfg = REG.launch_config(arch, full, layers)
    opt = OPT.OptConfig(warmup_steps=1, total_steps=TRAIN_STEPS)
    data = DATA.PackedDocsLM(cfg, docs, block=block, seed=0)
    psched = OPS.make_packed_sched(data.member_lens, block=block,
                                   window=cfg.sliding_window)
    step = TS.make_train_step(cfg, opt, attn_impl="pallas", packed=psched,
                              block=block)
    init = lambda: TS.init_state(jax.random.key(0), cfg, opt)

    def batch(i):
        parts = [data.batch(i * rows + r) for r in range(rows)]
        return {k: jnp.concatenate([p[k] for p in parts]) for k in parts[0]}
    return cfg, step, init, batch


def phase_training(steps=TRAIN_STEPS, **kw):
    reset_counters()
    cfg, step, init, batch = train_setup(**kw)
    state = jax.jit(init)()
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    log(f"  train: {cfg.name} layers={cfg.n_layers} (of 48) d_model="
        f"{cfg.d_model} params={n_params / 1e9:.3f}B packed rows="
        f"{batch(0)['tokens'].shape[1]}")
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch(0)).compile()
    mem = compiled.memory_analysis()
    log(f"  compile {time.perf_counter() - t0:.1f}s; temp "
        f"{mem.temp_size_in_bytes} B, arguments "
        f"{mem.argument_size_in_bytes} B")
    for i in range(steps):
        t1 = time.perf_counter()
        state, metrics = compiled(state, batch(i))
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        log(f"  step {i}: loss={loss:.4f} grad_norm={gnorm:.4f} "
            f"({time.perf_counter() - t1:.3f}s)")
        if not (np.isfinite(loss) and np.isfinite(gnorm)):
            raise AssertionError("non-finite loss or gradient norm")
    launches(["tri_attn.packed_fwd", "tri_attn.packed_bwd_dq",
              "tri_attn.packed_bwd_dkv"])
    del state, compiled
    gc.collect()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(**kw):
    """One loss/grad step of the packed train step on a (data=2, model=2)
    mesh with the repo's sharding rules, against the same step on one
    chip."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.parallel import hints as HN
    from repro.parallel import sharding as SH
    from repro.train import train_step as TS

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, got "
                             f"{len(devices)}")
    reset_counters()
    cfg, step, init, batch = train_setup(rows=2, **kw)
    b0 = batch(0)
    log(f"  {cfg.name} layers={cfg.n_layers} (of 48) d_model={cfg.d_model}, "
        f"2 packed rows of {b0['tokens'].shape[1]}")
    results = {}

    # one chip
    t0 = time.perf_counter()
    state = jax.jit(init)()
    new_state, met = jax.jit(step, donate_argnums=(0,))(state, b0)
    results["one"] = (float(met["loss"]), float(met["grad_norm"]))
    del state, new_state, met
    gc.collect()
    log(f"  one chip: loss={results['one'][0]:.6f} grad_norm="
        f"{results['one'][1]:.6f} ({time.perf_counter() - t0:.1f}s)")

    # (data=2, model=2) mesh of the four devices
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    shapes = jax.eval_shape(init)
    ps = SH.param_shardings(mesh, shapes.params)
    rep = NamedSharding(mesh, P())
    st_sh = TS.TrainState(params=ps, opt_state={"m": ps, "v": ps},
                          step=rep, err_state=None)
    b_sh = SH.batch_shardings(mesh, b0)
    t0 = time.perf_counter()
    state = jax.jit(init, out_shardings=st_sh)()
    batch_dev = jax.device_put(b0, b_sh)
    with HN.hints(attn_qkv=NamedSharding(
            mesh, P(SH.dp_axes(mesh), "model", None, None))):
        compiled = jax.jit(step, in_shardings=(st_sh, b_sh),
                           donate_argnums=(0,)).lower(state,
                                                      batch_dev).compile()
    hlo = compiled.as_text()
    new_state, met = compiled(state, batch_dev)
    results["mesh"] = (float(met["loss"]), float(met["grad_norm"]))
    log(f"  mesh (data=2, model=2): loss={results['mesh'][0]:.6f} "
        f"grad_norm={results['mesh'][1]:.6f} "
        f"({time.perf_counter() - t0:.1f}s); tpu_custom_call in HLO: "
        f"{'tpu_custom_call' in hlo}; all-reduce ops "
        f"{hlo.count('all-reduce(')} all-gather ops "
        f"{hlo.count('all-gather(')}")
    # placement: every device holds its own shards of a model-parallel
    # weight, not all of them on device 0
    wq = new_state.params["layers"]["l0"]["mixer"]["wq"]
    shards = {s.device.id: (s.index, s.data.shape)
              for s in wq.addressable_shards}
    log(f"  wq {wq.shape} shards: {shards}")
    if len(shards) != 4 or len({str(v[0]) for v in shards.values()}) != 4:
        raise AssertionError("wq is not split over the four devices")
    for dev in devices:
        log("  " + memory_line(f"device {dev.id}", dev))
    # f32 accumulation order differs between the sharded and the one-chip
    # program and the gradient passes bf16 weights: agree to 2e-3 relative
    # on the loss and 2e-2 on the gradient norm
    check("loss rel diff (mesh vs one chip)",
          abs(results["mesh"][0] - results["one"][0])
          / abs(results["one"][0]), 2e-3)
    check("grad_norm rel diff (mesh vs one chip)",
          abs(results["mesh"][1] - results["one"][1])
          / abs(results["one"][1]), 2e-2)
    launches(["tri_attn.packed_fwd", "tri_attn.packed_bwd_dq",
              "tri_attn.packed_bwd_dkv"])


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on a 2x2 mesh "
                         "and its one-chip comparison")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import place_compile_cache

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {place_compile_cache()}")
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("kernels", phase_kernels),
               ("serving_split", lambda: phase_serving("split")),
               ("serving_fused", lambda: phase_serving("fused")),
               ("training", phase_training)])
    if not args.four_chips:
        log(f"cuts: serving {ARCH} {SERVE_LAYERS}/48 layers, training "
            f"{TRAIN_LAYERS}/48 layers; every width as published")
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"== phase {name}")
        fn()
        log(f"== phase {name} ok in {time.perf_counter() - t0:.1f}s; "
            + memory_line(name))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
