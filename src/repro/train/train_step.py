"""The sharded training step: loss -> grads -> optimizer, with gradient
accumulation (lax.scan microbatching) and optional int8 grad compression.

The LM-head logits ((B, S, padded_vocab) f32 — up to 4 TB global for the
256k-vocab archs at train_4k) are never materialized across the whole batch:
cross-entropy is computed inside each microbatch shard of the scan.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import model as MD
from repro.obs import metrics as MET
from repro.train import optimizer as OPT


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array  # () int32
    err_state: Any = None  # int8-compression error feedback (optional)


def init_state(key, cfg, opt: OPT.OptConfig, *, compression: bool = False):
    params = MD.init_params(key, cfg)
    state = TrainState(
        params=params,
        opt_state=OPT.init_opt_state(opt, params),
        step=jnp.zeros((), jnp.int32),
        err_state=None,
    )
    if compression:
        from repro.parallel import compression as C
        state.err_state = C.init_error_state(params)
    return state


def make_train_step(cfg, opt: OPT.OptConfig, *, microbatches: int = 1,
                    attn_impl: str = "scan", remat: bool = True,
                    aux_weight: float = 0.01, block: int = 512,
                    compressed_allreduce=None, act_sharding=None,
                    packed=None):
    """Returns train_step(state, batch) -> (state, metrics).

    microbatches M > 1 splits the global batch's leading dim into M
    sequential grad-accumulation steps (activation memory / M).
    compressed_allreduce: optional (grads, err) -> (grads, err) hook from
    parallel/compression.make_compressed_allreduce.
    act_sharding: NamedSharding for the layer-scan activation carry.
    packed: optional PackedTriSched — ragged document-batch training over
    the packed layout (train/data.pack_documents builds the batches; the
    schedule is static, so one program serves every step of that packing).
    Batches must then carry "positions" and "mask" alongside tokens/labels.
    """

    def loss_fn(params, mb):
        return MD.loss_fn(params, cfg, mb, attn_impl=attn_impl, remat=remat,
                          aux_weight=aux_weight, block=block,
                          act_sharding=act_sharding, packed=packed)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch):
        params = state.params

        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])

            mbs = jax.tree.map(split, batch)

            def mb_step(acc, mb):
                (l, met), g = grad_fn(params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + l), met

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), mets = jax.lax.scan(
                mb_step, (zero_g, jnp.zeros((), jnp.float32)), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics = jax.tree.map(jnp.mean, mets)

        err = state.err_state
        if compressed_allreduce is not None and err is not None:
            grads, err = compressed_allreduce(grads, err)

        new_params, new_opt, opt_metrics = OPT.apply_updates(
            opt, params, grads, state.opt_state, state.step)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        new_state = TrainState(params=new_params, opt_state=new_opt,
                               step=state.step + 1, err_state=err)
        return new_state, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serving steps (lowered by the dry-run for decode shapes)
# ---------------------------------------------------------------------------


def make_serve_step(cfg):
    """serve_step(params, cache, tokens, pos) -> (next_token_logits, cache).

    One new token per sequence against a KV cache / recurrent state of
    seq_len (the decode_* / long_* shape cells)."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = MD.decode_step(params, cfg, cache, tokens, pos)
        return logits[:, -1], cache

    return serve_step


def make_prefill_step(cfg, *, attn_impl: str = "scan", block: int = 512,
                      cache_dtype=jnp.bfloat16):
    """prefill_step(params, batch) -> (last-position logits, decode cache)."""

    def prefill_step(params, batch):
        MET.counter_inc("prefill_step_calls", 1, {"impl": attn_impl})
        s_total = (batch["tokens"].shape[1] if "tokens" in batch else 0)
        if "embeds" in batch:
            s_total += batch["embeds"].shape[1]
        hidden, cache = MD.prefill_cache(params, cfg, batch, s_total,
                                         attn_impl=attn_impl, block=block,
                                         cache_dtype=cache_dtype)
        logits = MD.logits_from_hidden(params, cfg, hidden[:, -1:])
        return logits[:, 0], cache

    return prefill_step
