"""Architecture registry: --arch <id> -> ModelConfig, shape cells, and
ShapeDtypeStruct input specs for the dry-run (no device allocation).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, reduced

ARCH_IDS = (
    "mixtral-8x7b",
    "granite-moe-3b-a800m",
    "rwkv6-1.6b",
    "yi-9b",
    "nemotron-4-340b",
    "llama3-405b",
    "granite-34b",
    "musicgen-large",
    "internvl2-1b",
    "jamba-1.5-large-398b",
)


def _module_name(arch: str) -> str:
    return "repro.configs." + arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    return importlib.import_module(_module_name(arch)).CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


# ---------------------------------------------------------------------------
# Cell support matrix (arch x shape)
# ---------------------------------------------------------------------------


def supported(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether this (arch, shape) cell runs; reason when skipped."""
    if shape.kind == "long_decode" and cfg.is_pure_full_attention():
        return False, ("pure full-attention arch: 500k decode is quadratic "
                       "with an unbounded KV cache; skipped per brief "
                       "(sub-quadratic archs run it)")
    return True, ""


def runnable_cells():
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, why = supported(cfg, shape)
            out.append((arch, sname, ok, why))
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; weak-type-correct, shardable)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                act_dtype=jnp.bfloat16) -> dict:
    """Train/prefill batch: the model inputs for one global step."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.frontend == "audio_frames":
        # EnCodec frame embeddings precomputed by the stub frontend.
        return {
            "embeds": _sds((b, s, cfg.d_model), act_dtype),
            "labels": _sds((b, s), jnp.int32),
        }
    if cfg.frontend == "vision_patches":
        p = cfg.n_patches
        return {
            "embeds": _sds((b, p, cfg.d_model), act_dtype),
            "tokens": _sds((b, s - p), jnp.int32),
            "labels": _sds((b, s), jnp.int32),
            "mask": _sds((b, s), jnp.float32),
        }
    return {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, shape: ShapeConfig,
                cache_dtype=jnp.bfloat16) -> dict:
    """Decode-state pytree spec (KV cache of seq_len / recurrent states)."""
    from repro.models import model as MD
    b, s = shape.global_batch, shape.seq_len
    return jax.eval_shape(
        lambda: MD.init_cache(cfg, b, s, cache_dtype))


def decode_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b = shape.global_batch
    return {
        "tokens": _sds((b, 1), jnp.int32),
        "pos": _sds((), jnp.int32),
    }


def params_specs(cfg: ModelConfig):
    from repro.models import model as MD
    key = jax.random.key(0)
    return jax.eval_shape(lambda: MD.init_params(key, cfg))


def input_specs(arch: str, shape_name: str) -> dict:
    """Everything dryrun.py needs for one cell, as ShapeDtypeStructs."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    specs = {"params": params_specs(cfg)}
    if shape.is_decode:
        specs["cache"] = cache_specs(cfg, shape)
        specs.update(decode_specs(cfg, shape))
    else:
        specs["batch"] = batch_specs(cfg, shape)
    return specs


def smoke_config(arch: str, **kw) -> ModelConfig:
    return reduced(get_config(arch), **kw)


def launch_config(arch: str, full: bool, layers: int = 0) -> ModelConfig:
    """The config an entry point runs: reduced widths, or the published
    ones cut to ``layers`` (0 = all) — depth is the only cut at full
    width."""
    if not full:
        return smoke_config(arch)
    cfg = get_config(arch)
    return dataclasses.replace(cfg, n_layers=layers) if layers else cfg
