import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the device
# count at first init). Only this process sees 512 placeholder devices;
# tests and benches see the single real CPU device.

import argparse      # noqa: E402
import functools     # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import registry as REG           # noqa: E402
from repro.launch import mesh as MESH               # noqa: E402
from repro.parallel import sharding as SH           # noqa: E402
from repro.roofline import hlo_parse as HLO         # noqa: E402
from repro.roofline import model as RF              # noqa: E402
from repro.train import optimizer as OPT            # noqa: E402
from repro.train import train_step as TS            # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding rules produce a partitionable program (no mismatch),
  * it fits (memory_analysis bytes/device),
  * and it yields the roofline terms (trip-count-corrected HLO FLOPs /
    HBM-traffic bytes / collective bytes -> §Roofline).

Results are cached one JSON per cell under artifacts/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

# Per-arch training knobs (chosen by activation-memory napkin math; the
# global batch is 256 so microbatch counts must keep B/mb divisible by the
# 32-way multi-pod DP axis). Adafactor for the >=300B archs: AdamW moments
# (8 bytes/param) alone would exceed v5e HBM at 256 chips.
TRAIN_KNOBS = {
    # arch: (optimizer, microbatches)
    "mixtral-8x7b": ("adamw", 4),
    "granite-moe-3b-a800m": ("adamw", 8),  # mb=1 peaks 101 GiB/dev (§Perf G1)
    "rwkv6-1.6b": ("adamw", 1),
    "yi-9b": ("adamw", 4),
    "nemotron-4-340b": ("adafactor", 8),
    "llama3-405b": ("adafactor", 8),
    "granite-34b": ("adamw", 4),
    "musicgen-large": ("adamw", 1),
    "internvl2-1b": ("adamw", 1),
    "jamba-1.5-large-398b": ("adafactor", 8),
}

ATTN_BLOCK = 512

# §Perf optimization passes (see parallel/hints.py + EXPERIMENTS.md §Perf).
# "opts" is a comma-set: attn_tp,moe_local,act_seq,mb=<n>
OPT_CHOICES = ("attn_tp", "moe_local", "act_seq")


def _act_sharding(mesh):
    dp = SH.dp_axes(mesh)
    return NamedSharding(mesh, P(dp, None, "model"))


def auto_opts(cfg, mesh, shape) -> tuple:
    """Per-(arch, shape) defaults found by the §Perf hill-climb:

    * moe_local — grouped per-DP-shard dispatch, only when the expert count
      does NOT divide the DP axis (otherwise plain EP sharding is already
      active and grouping fights it: jamba regression, EXPERIMENTS §Perf)
      and only for token-heavy shapes (train/prefill; decode dispatch is
      tiny and the constraints just force reshards).
    * attn_rep — replicated attention when heads don't divide the TP axis;
      training only (the backward per-tile score all-reduces are what it
      removes; at prefill the baseline propagation is already fine).
    """
    model_size = mesh.shape["model"]
    dp_size = SH._axis_size(mesh, SH.dp_axes(mesh))
    opts = []
    if cfg.n_experts and cfg.n_experts % dp_size != 0 \
            and not shape.is_decode:
        opts.append("moe_local")
    if cfg.n_heads % model_size and "attn" in cfg.layer_kinds \
            and shape.kind == "train":
        opts.append("attn_rep")
    return tuple(opts)


def _opt_hints(mesh, cfg, opts) -> dict:
    """Translate --opt flags into sharding hints valid for this cell."""
    from repro.parallel import hints as HN  # noqa: F401 (context applied by caller)
    dp = SH.dp_axes(mesh)
    model_size = mesh.shape["model"]
    hint = {}
    if "attn_tp" in opts and cfg.n_heads % model_size == 0:
        hint["attn_qkv"] = NamedSharding(mesh, P(dp, "model", None, None))
    if "moe_local" in opts and cfg.n_experts:
        dp_size = SH._axis_size(mesh, dp)
        hint["moe_groups"] = dp_size
        hint["moe_buf"] = NamedSharding(mesh, P(dp, None, None, None))
        hint["moe_buf3"] = NamedSharding(mesh, P(dp, None, None))
    if "moe_gather" in opts and cfg.n_experts:
        hint["moe_wi"] = NamedSharding(mesh, P(None, None, "model"))
        hint["moe_wo"] = NamedSharding(mesh, P(None, "model", None))
    if "attn_rep" in opts:
        hint["attn_qkv"] = NamedSharding(mesh, P(dp, None, None, None))
    if "act_seq" in opts:
        hint["act_seq"] = NamedSharding(mesh, P(dp, "model", None))
    if "remat_attn" in opts:
        hint["remat_policy"] = ("attn_out",)
    return hint


def build_cell(arch: str, shape_name: str, mesh, opts=()):
    """Returns (jitted_fn, arg_specs tuple) for one cell."""
    cfg = REG.get_config(arch)
    shape = REG.get_shape(shape_name)
    params = REG.params_specs(cfg)
    overrides = None
    if "embed_dp" in opts:
        # vocab replicated, d sharded over EVERY axis: token gather and its
        # scatter-add gradient become collective-free (§Perf G6/L6)
        overrides = {"embed": P(None, tuple(mesh.axis_names))}
    p_sh = SH.param_shardings(mesh, params, overrides=overrides)

    if shape.is_decode:
        serve = TS.make_serve_step(cfg)
        cache = REG.cache_specs(cfg, shape)
        c_sh = SH.cache_shardings(mesh, cache)
        dspec = REG.decode_specs(cfg, shape)
        t_sh = SH.token_shardings(mesh, dspec)
        fn = jax.jit(
            serve,
            in_shardings=(p_sh, c_sh, t_sh["tokens"], t_sh["pos"]),
            out_shardings=(None, c_sh),
            donate_argnums=(1,),
        )
        return fn, (params, cache, dspec["tokens"], dspec["pos"])

    batch = REG.batch_specs(cfg, shape)
    b_sh = SH.batch_shardings(mesh, batch)

    if shape.kind == "prefill":
        prefill = TS.make_prefill_step(cfg, attn_impl="scan",
                                       block=ATTN_BLOCK)
        cache_out = REG.cache_specs(cfg, shape)
        c_sh = SH.cache_shardings(mesh, cache_out)
        fn = jax.jit(prefill, in_shardings=(p_sh, b_sh),
                     out_shardings=(None, c_sh))
        return fn, (params, batch)

    # train
    opt_kind, microbatches = TRAIN_KNOBS[arch]
    for o in opts:
        if o.startswith("mb="):
            microbatches = int(o[3:])
    opt = OPT.OptConfig(kind=opt_kind)
    opt_state = jax.eval_shape(lambda p: OPT.init_opt_state(opt, p), params)
    o_sh = SH.param_shardings(mesh, opt_state, overrides=overrides)
    state = TS.TrainState(params=params, opt_state=opt_state,
                          step=jax.ShapeDtypeStruct((), jnp.int32),
                          err_state=None)
    s_sh = TS.TrainState(params=p_sh, opt_state=o_sh,
                         step=NamedSharding(mesh, P()), err_state=None)
    act = None if "act_seq" in opts else _act_sharding(mesh)
    step_fn = TS.make_train_step(
        cfg, opt, microbatches=microbatches, attn_impl="scan",
        remat=True, block=ATTN_BLOCK, act_sharding=act)
    fn = jax.jit(step_fn, in_shardings=(s_sh, b_sh),
                 out_shardings=(s_sh, None), donate_argnums=(0,))
    return fn, (state, batch)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, save_hlo: bool = False,
             opts=(), tag: str = "") -> dict:
    suffix = f"__{tag}" if tag else ""
    out_path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = REG.get_config(arch)
    shape = REG.get_shape(shape_name)
    ok, why = REG.supported(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "supported": ok, "skip_reason": why,
        "opts": sorted(opts), "tag": tag,
    }
    if not ok:
        _dump(out_path, rec)
        return rec

    mesh = MESH.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if "auto" in opts:
        opts = tuple(o for o in opts if o != "auto") \
            + auto_opts(cfg, mesh, shape)
        rec["opts"] = sorted(set(opts))
    n_chips = mesh.size
    rec["n_chips"] = n_chips
    rec["mesh_shape"] = dict(zip(mesh.axis_names,
                                 [int(s) for s in mesh.devices.shape]))
    try:
        from repro.parallel import hints as HN
        t0 = time.time()
        with mesh, HN.hints(**_opt_hints(mesh, cfg, opts)):
            fn, arg_specs = build_cell(arch, shape_name, mesh, opts=opts)
            lowered = fn.lower(*arg_specs)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
        if save_hlo:
            import gzip
            hlo_path = out_path.replace(".json", ".hlo.gz")
            with gzip.open(hlo_path, "wt") as f:
                f.write(compiled.as_text())
        analysis = HLO.analyze_compiled(compiled)
        rec["analysis"] = {k: v for k, v in analysis.items()}
        mf = RF.model_flops(cfg, shape)
        # the dry-run lowers for the v5e pod (launch/mesh.py)
        terms = RF.terms_from_analysis(analysis, n_chips=n_chips,
                                       device_kind=RF.V5E, model_flops=mf)
        rec["roofline"] = terms.as_dict()
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _dump(out_path, rec)
    return rec


def _dump(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def run_tri_3body_cell(out_dir: str, *, n_rows: int = 256, block: int = 8,
                       d: int = 8, strict: bool = False,
                       force: bool = False) -> dict:
    """Roofline cell for the tri_3body kernel family (ROADMAP open item):
    lower + compile the tet-grid scan AND the BB-3D baseline scan, and
    record their trip-count-corrected FLOPs / HBM bytes so the 6x launch
    reduction shows up in artifacts alongside the model cells."""
    from repro.core import mapping as M
    from repro.kernels.tri_3body import ops as OPS3

    tag = f"n{n_rows}_b{block}_d{d}" + ("_strict" if strict else "")
    out_path = os.path.join(out_dir, f"kernel__tri_3body__{tag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    n = n_rows // block
    rec = {"kernel": "tri_3body", "n_rows": n_rows, "block": block,
           "d": d, "strict": strict,
           "tiles_tet": M.tet(n), "tiles_bb3": n ** 3,
           "launch_reduction": (n ** 3) / M.tet(n)}
    x = jax.ShapeDtypeStruct((n_rows, d), jnp.float32)
    try:
        for name, impl in (("tet", "scan"), ("bb3", "bb3_scan")):
            fn = jax.jit(functools.partial(
                OPS3.three_body, block=block, impl=impl, strict=strict))
            t0 = time.time()
            compiled = fn.lower(x).compile()
            an = HLO.analyze_compiled(compiled)
            rec[name] = {
                "compile_s": round(time.time() - t0, 2),
                "flops": an["flops"],
                "hbm_bytes": an["hbm_bytes"],
                "intensity_flops_per_byte":
                    an["flops"] / max(an["hbm_bytes"], 1.0),
            }
        rec["flops_ratio_bb3_over_tet"] = (
            rec["bb3"]["flops"] / max(rec["tet"]["flops"], 1.0))
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _dump(out_path, rec)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: attn_tp,moe_local,act_seq,mb=<n>")
    ap.add_argument("--tag", default="",
                    help="suffix for the output JSON (A/B experiments)")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--kernel", default=None, choices=["tri_3body"],
                    help="dry-run a standalone kernel cell instead of the "
                         "(arch x shape x mesh) grid")
    ap.add_argument("--kernel-n-rows", type=int, default=256)
    ap.add_argument("--kernel-block", type=int, default=8)
    ap.add_argument("--kernel-d", type=int, default=8)
    ap.add_argument("--strict", action="store_true",
                    help="tri_3body: a > b > c in-kernel masking")
    args = ap.parse_args()
    opts = tuple(o for o in args.opt.split(",") if o)

    if args.kernel == "tri_3body":
        rec = run_tri_3body_cell(
            args.out, n_rows=args.kernel_n_rows, block=args.kernel_block,
            d=args.kernel_d, strict=args.strict, force=args.force)
        status = "ok" if rec.get("ok") else "FAIL " + rec.get("error", "")
        print(f"kernel tri_3body {status} tiles "
              f"{rec['tiles_tet']}/{rec['tiles_bb3']} "
              f"flops bb3/tet={rec.get('flops_ratio_bb3_over_tet', 0):.2f}")
        return

    archs = REG.ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = (list(REG.SHAPES) if (args.all or args.shape is None)
              else [args.shape])
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                t0 = time.time()
                rec = run_cell(arch, shape_name, mesh_kind, args.out,
                               force=args.force, save_hlo=args.save_hlo,
                               opts=opts, tag=args.tag)
                status = ("SKIP " + rec.get("skip_reason", "")[:40]
                          if not rec.get("supported", True)
                          else "ok" if rec.get("ok") else
                          "FAIL " + rec.get("error", "")[:80])
                dom = rec.get("roofline", {}).get("dominant", "-")
                print(f"[{time.time()-t0:7.1f}s] {mesh_kind:6s} {arch:24s} "
                      f"{shape_name:12s} {status} dom={dom}", flush=True)


if __name__ == "__main__":
    main()
