"""Plain reference of the served decoder, in float32 at full precision.

Written from the layer equations of the configurations' papers (Yi,
arXiv 2403.04652; Granite code models, arXiv 2405.04324) as this
benchmark's configuration files state them, and independent of the
program: it imports nothing of ``repro`` and takes no array the program
made. Weights are made again, block by block, from the seed
(``weights.layer_weights``), with the values they are served in.

The functions below (``run``, ``served_gaps``, ``control_gaps``,
``params``, ``logits``, ``flat_params``, ``train_loss``,
``train_reference``) take a family's model object (bench/families/, its
``Reference``): a frozen, hashable dataclass with ``d``, ``vocab``,
``eps``, ``dtype`` (what the matrices are served in), ``blocks`` (the
length of the program's stacked layer axis: one block is one scan step,
so ``layers/l{p}/...`` for each layer p of the pattern),
``block_shapes()`` and ``served_dtypes()`` (one block's leaf paths, their
shapes and served dtypes), ``block(wts, x, prec)`` over (B, S, d) at
positions 0..S-1 and, for training, ``train_block(wts, x, prec, docs)``
over the real tokens of packed documents. The embedding, the final norm
and the head are these functions' own. ``Model`` is the dense family's.

Each dense layer is pre-norm residual:

  h = RMSNorm(x) * g1;  q, k, v = h Wq, h Wk, h Wv;  RoPE (rotate-half)
  on q and k;  o = causal softmax(q k^T / sqrt(hd)) v, query head i
  reading kv head i // (H / Hkv);  x += o Wo
  h = RMSNorm(x) * g2;  x += (silu(h Wg) * (h Wi)) Wo   (SwiGLU)
                       or gelu_tanh(h Wi) Wo            (GELU)

then RMSNorm * g and the untied head. ``prec="f32"`` computes every
matmul in float32 at ``Precision.HIGHEST``; ``prec="fp8"`` is the
control, one precision below the bfloat16 the configurations serve: the
scaled fp8 of fp8 training and serving, where every matmul rounds its
operands to float8_e4m3fn (and, in the backward, its incoming gradient
to float8_e5m2) under a per-tensor amax scale and sums in float32.

Sequences are processed together, padded to one length (causal masking
keeps the padded tail out of every real row), one layer at a time, each
sequence and group of heads in turn, so a 16-layer model at full width
fits beside nothing else on one chip. Training (``train_reference``)
runs a packed row one document at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

# where each weight lives in the served parameter tree (layer leaves carry
# a leading layer axis there; here they are one layer's slice)
LAYER = {"attn_norm": "layers/l0/norm1", "wq": "layers/l0/mixer/wq",
         "wk": "layers/l0/mixer/wk", "wv": "layers/l0/mixer/wv",
         "wo": "layers/l0/mixer/wo", "mlp_norm": "layers/l0/norm2",
         "w_up": "layers/l0/ffn/wi", "w_gate": "layers/l0/ffn/wg",
         "w_down": "layers/l0/ffn/wo"}
TOP = {"embed": "embed", "final_norm": "final_norm", "head": "lm_head"}
HEAD_CHUNK = 8  # query heads per attention block


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    act: str          # "swiglu" | "gelu"
    rope_theta: float
    eps: float
    dtype: str        # the dtype the matrices are served in

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ff=c["intermediate_size"],
                   vocab=c["vocab_size"], layers=c["num_hidden_layers"],
                   act=c["mlp"], rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])

    @property
    def blocks(self) -> int:
        return self.layers

    def block(self, wts, x, prec: str):
        return _layer(wts, x, self, prec)

    def train_block(self, wts, x, prec: str, docs: Tuple[int, ...]):
        return _train_layer(wts, x, self, prec, docs)

    def block_shapes(self) -> Dict[str, tuple]:
        d, hd, f = self.d, self.head_dim, self.ff
        s = {LAYER["attn_norm"]: (d,), LAYER["mlp_norm"]: (d,),
             LAYER["wq"]: (d, self.heads * hd),
             LAYER["wk"]: (d, self.kv_heads * hd),
             LAYER["wv"]: (d, self.kv_heads * hd),
             LAYER["wo"]: (self.heads * hd, d),
             LAYER["w_up"]: (d, f), LAYER["w_down"]: (f, d)}
        if self.act == "swiglu":
            s[LAYER["w_gate"]] = (d, f)
        return s

    def served_dtypes(self) -> Dict[str, object]:
        mat = jnp.dtype(self.dtype)
        return {p: (jnp.float32 if len(s) == 1 else mat)
                for p, s in self.block_shapes().items()}


def _mm32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _quant(x, dtype):
    """``x`` rounded to the fp8 ``dtype`` under one scale for the whole
    tensor, which maps its largest magnitude onto the format's largest,
    then scaled back to float32."""
    x = x.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_fp8(a, b):
    return _mm32(_quant(a, jnp.float8_e4m3fn), _quant(b, jnp.float8_e4m3fn))


def _mm_fp8_fwd(a, b):
    qa, qb = _quant(a, jnp.float8_e4m3fn), _quant(b, jnp.float8_e4m3fn)
    return _mm32(qa, qb), (qa, qb)


def _mm_fp8_bwd(res, g):
    _, vjp = jax.vjp(_mm32, *res)
    return vjp(_quant(g, jnp.float8_e5m2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(a, b, prec: str):
    """A matmul of the reference: float32 at ``HIGHEST``, or the control's
    scaled fp8 (operands e4m3 and the backward's incoming gradient e5m2,
    each under a per-tensor scale; products summed in float32)."""
    if prec == "fp8":
        return _mm_fp8(a, b)
    return _mm32(a, b)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, n, hd) at positions ``pos`` (S,); rotate-half pairs
    (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _head_chunk(group: int) -> int:
    return max(c for c in range(1, HEAD_CHUNK + 1) if group % c == 0)


def _attention(q, k, v, m: Model, prec: str):
    """Causal attention of one sequence: q (S, H, hd); k, v (S, Hkv, hd)
    -> (S, H * hd)."""
    s = q.shape[0]
    group = m.heads // m.kv_heads
    chunk = _head_chunk(group)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    outs = []
    for h0 in range(0, m.heads, chunk):
        kv = h0 // group
        qh = q[:, h0:h0 + chunk].transpose(1, 0, 2)        # (c, S, hd)
        sc = _mm(qh, k[:, kv].T, prec) / math.sqrt(m.head_dim)
        sc = jnp.where(causal[None], sc, -jnp.inf)          # (c, S, S)
        outs.append(_mm(jax.nn.softmax(sc, axis=-1), v[:, kv], prec))
    o = jnp.concatenate(outs, axis=0)                       # (H, S, hd)
    return o.transpose(1, 0, 2).reshape(s, m.heads * m.head_dim)


def _layer(wts, x, m: Model, prec: str):
    """One layer over each sequence of x (B, S, d), one at a time, at
    positions 0..S-1."""
    pos = jnp.arange(x.shape[1])

    def one(xs):
        s = xs.shape[0]
        h = _rms(xs, wts[LAYER["attn_norm"]], m.eps)
        q = _mm(h, wts[LAYER["wq"]], prec).reshape(s, m.heads, m.head_dim)
        k = _mm(h, wts[LAYER["wk"]], prec).reshape(s, m.kv_heads,
                                                   m.head_dim)
        v = _mm(h, wts[LAYER["wv"]], prec).reshape(s, m.kv_heads,
                                                   m.head_dim)
        q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
        xs = xs + _mm(_attention(q, k, v, m, prec), wts[LAYER["wo"]], prec)
        return xs + _mlp(wts, xs, m, prec)
    return jax.lax.map(one, x)


def _mlp(wts, x, m: Model, prec: str):
    h = _rms(x, wts[LAYER["mlp_norm"]], m.eps)
    if m.act == "swiglu":
        a = (jax.nn.silu(_mm(h, wts[LAYER["w_gate"]], prec))
             * _mm(h, wts[LAYER["w_up"]], prec))
    else:
        a = jax.nn.gelu(_mm(h, wts[LAYER["w_up"]], prec), approximate=True)
    return _mm(a, wts[LAYER["w_down"]], prec)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _block_step(key, block, x, m, prec: str):
    wts = W.layer_weights(key, block, m.block_shapes(), m.served_dtypes())
    return m.block(wts, x, prec)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(key, tokens, m):
    table = W.leaf_values(key, TOP["embed"], (m.vocab, m.d))
    return jnp.take(table.astype(m.dtype).astype(jnp.float32), tokens,
                    axis=0)


@functools.partial(jax.jit, static_argnames=("m", "prec"))
def _head(key, x_rows, score, m, prec: str):
    """x_rows: (R, d) final hidden rows; score: (K, R) tokens to score.
    Returns (argmax (R,), gaps (K, R)): each scored token's logit below
    the row's best."""
    g = W.leaf_values(key, TOP["final_norm"], (m.d,))
    head = W.leaf_values(key, TOP["head"], (m.d, m.vocab))
    head = head.astype(m.dtype).astype(jnp.float32)
    logits = _mm(_rms(x_rows, g, m.eps), head, prec)
    best = jnp.max(logits, axis=-1)
    picked = jnp.take_along_axis(logits[None], score[..., None], axis=-1)
    return jnp.argmax(logits, -1).astype(jnp.int32), best[None] - picked[
        ..., 0]


def run(m, seed: int, seqs: Sequence[np.ndarray],
        rows: Sequence[np.ndarray], prec: str = "f32",
        score: Optional[Sequence[Sequence[np.ndarray]]] = None,
        pad_to: int = 0, batch: int = 0) -> dict:
    """The reference over ``seqs`` (token arrays), read at ``rows`` (per
    sequence, the positions whose next-token logits are wanted).

    ``score[j][i]`` are tokens, one per row of sequence i, whose gap below
    that row's best logit is returned. Sequences are padded to ``pad_to``
    tokens (at least the longest) and the batch to ``batch`` sequences,
    so a cell compiles the same programs in every run. Returns
    {"argmax": [per sequence (n_i,)], "gaps": [per score set, per sequence
    (n_i,)]} as host arrays."""
    score = list(score or [])
    key = W.base_key(seed)
    n_seq = len(seqs)
    s_pad = max(pad_to, max(len(s) for s in seqs))
    b = max(batch, n_seq)
    tokens = np.zeros((b, s_pad), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    x = _embed(key, jnp.asarray(tokens), m)
    for block in range(m.blocks):
        x = _block_step(key, jnp.int32(block), x, m, prec)
    n_rows = max(len(r) for r in rows)
    flat = np.zeros((b, n_rows), np.int32)
    sc = np.zeros((len(score), b, n_rows), np.int32)
    for i, r in enumerate(rows):
        flat[i, :len(r)] = r
        for j in range(len(score)):
            sc[j, i, :len(r)] = score[j][i]
    x_rows = jnp.take_along_axis(x, jnp.asarray(flat)[..., None], axis=1)
    del x
    am, gaps = _head(key, x_rows.reshape(b * n_rows, m.d),
                     jnp.asarray(sc.reshape(len(score), b * n_rows)), m,
                     prec)
    am = np.asarray(am).reshape(b, n_rows)
    gaps = np.asarray(gaps).reshape(len(score), b, n_rows)
    return {"argmax": [am[i, :len(r)] for i, r in enumerate(rows)],
            "gaps": [[gaps[j, i, :len(r)] for i, r in enumerate(rows)]
                     for j in range(len(score))]}


def served_gaps(m, seed: int, served: List[tuple], *,
                pad_to: int = 0, batch: int = 0, prec: str = "f32"
                ) -> List[np.ndarray]:
    """For each (prompt, served tokens): how far each served token's
    reference logit lies below the reference's best at that position.
    Token j was produced from prompt + served[:j]."""
    seqs, rows, toks = [], [], []
    for prompt, out in served:
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        toks.append(out)
    res = run(m, seed, seqs, rows, prec=prec, score=[toks], pad_to=pad_to,
              batch=batch)
    return res["gaps"][0]


def control_gaps(m, seed: int, served: List[tuple], *,
                 pad_to: int = 0, batch: int = 0) -> List[np.ndarray]:
    """The control: at each position of the same sequences, the token that
    the fp8 reference puts first, scored by the float32 reference."""
    seqs, rows = [], []
    for prompt, out in served:
        prompt = np.asarray(prompt, np.int32)
        out = np.asarray(out, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
    low = run(m, seed, seqs, rows, prec="fp8", pad_to=pad_to, batch=batch)
    res = run(m, seed, seqs, rows, prec="f32", score=[low["argmax"]],
              pad_to=pad_to, batch=batch)
    return res["gaps"][0]


# -- whole-model form (small models, training): explicit float32 params ------


def params(m, seed: int) -> dict:
    """Every weight as float32 arrays of the values served:
    {"embed", "final_norm", "head", "layers": [one dict per block]}."""
    key = W.base_key(seed)
    mat = jnp.dtype(m.dtype)
    top = {"embed": W.leaf_values(key, TOP["embed"], (m.vocab, m.d)
                                  ).astype(mat).astype(jnp.float32),
           "final_norm": W.leaf_values(key, TOP["final_norm"], (m.d,)),
           "head": W.leaf_values(key, TOP["head"], (m.d, m.vocab)
                                 ).astype(mat).astype(jnp.float32)}
    top["layers"] = [W.layer_weights(key, i, m.block_shapes(),
                                     m.served_dtypes())
                     for i in range(m.blocks)]
    return top


def logits(p: dict, m, tokens, prec: str = "f32"):
    """(B, S) tokens -> (B, S, V) float32 logits."""
    x = jnp.take(p["embed"], tokens, axis=0)
    for wts in p["layers"]:
        x = m.block(wts, x, prec)
    return _mm(_rms(x, p["final_norm"], m.eps), p["head"], prec)


# -- training: the first steps of AdamW over packed documents -----------------
#
# Parameters are keyed "<path>@<block>" (a layer leaf, one block's slice) or
# "<path>" (embedding, final norm, head), the keys the program's per-leaf
# norms use. Attention runs one document at a time over its real tokens
# (padding only ever sits at a document's causal tail, so leaving it out
# changes no real row); each group of heads and each block of rows of the
# cross-entropy is rematerialised, so the backward holds one block at a time.

CE_ROWS = 1024


def flat_params(m, seed: int) -> Dict[str, jax.Array]:
    p = params(m, seed)
    flat = {"embed": p["embed"], "final_norm": p["final_norm"],
            TOP["head"]: p["head"]}
    for i, wts in enumerate(p["layers"]):
        for path, value in wts.items():
            flat[f"{path}@{i}"] = value
    return flat


def _doc_attention(q, k, v, m: Model, prec: str):
    """Causal attention of one document: q (n, H, hd), k/v (n, Hkv, hd)."""
    n = q.shape[0]
    group = m.heads // m.kv_heads
    chunk = _head_chunk(group)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    @jax.checkpoint
    def heads(qh, kh, vh):
        sc = _mm(qh, kh.T, prec) / math.sqrt(m.head_dim)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        return _mm(jax.nn.softmax(sc, axis=-1), vh, prec)

    outs = [heads(q[:, h0:h0 + chunk].transpose(1, 0, 2), k[:, h0 // group],
                  v[:, h0 // group])
            for h0 in range(0, m.heads, chunk)]
    return jnp.concatenate(outs, 0).transpose(1, 0, 2).reshape(
        n, m.heads * m.head_dim)


def _train_layer(wts, x, m: Model, prec: str, docs: Tuple[int, ...]):
    """One layer over the real tokens x (T, d) of ``docs`` laid end to
    end, positions restarting at each document."""
    t = x.shape[0]
    pos = jnp.asarray(np.concatenate([np.arange(n) for n in docs]))
    h = _rms(x, wts[LAYER["attn_norm"]], m.eps)
    q = _mm(h, wts[LAYER["wq"]], prec).reshape(t, m.heads, m.head_dim)
    k = _mm(h, wts[LAYER["wk"]], prec).reshape(t, m.kv_heads, m.head_dim)
    v = _mm(h, wts[LAYER["wv"]], prec).reshape(t, m.kv_heads, m.head_dim)
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    outs, st = [], 0
    for n in docs:
        outs.append(_doc_attention(q[st:st + n], k[st:st + n], v[st:st + n],
                                   m, prec))
        st += n
    x = x + _mm(jnp.concatenate(outs, 0), wts[LAYER["wo"]], prec)
    return x + _mlp(wts, x, m, prec)


def train_loss(flat: Dict[str, jax.Array], tokens, labels, m,
               prec: str, docs: Tuple[int, ...]):
    """Mean next-token cross-entropy over the real tokens of one packed
    row: ``tokens``/``labels`` (T,) are the documents' real tokens end to
    end, T = sum(docs)."""
    x = jnp.take(flat["embed"], tokens, axis=0)
    for i in range(m.blocks):
        wts = {path: flat[f"{path}@{i}"] for path in m.block_shapes()}
        x = jax.checkpoint(lambda w, y: m.train_block(w, y, prec, docs))(
            wts, x)
    x = _rms(x, flat["final_norm"], m.eps)
    t = x.shape[0]
    pad = -t % CE_ROWS
    rows = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, CE_ROWS, m.d)
    lab = jnp.pad(labels, (0, pad)).reshape(-1, CE_ROWS)
    keep = jnp.pad(jnp.ones((t,), jnp.float32), (0, pad)).reshape(-1,
                                                                  CE_ROWS)

    @jax.checkpoint
    def block(hc, lc, kc):
        lg = _mm(hc, flat[TOP["head"]], prec)
        ll = jnp.take_along_axis(lg, lc[:, None], -1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - ll) * kc)

    total = sum(block(rows[i], lab[i], keep[i]) for i in range(rows.shape[0]))
    return total / t


def leaf_norms(flat: Dict[str, jax.Array]) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(
        {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in flat.items()}
    ).items()}


def decays(key: str) -> bool:
    """AdamW's weight decay reaches the matrices, not the norm scales."""
    name = key.split("@")[0].rsplit("/", 1)[-1]
    return not (name.startswith("norm") or name == "final_norm")


def train_reference(m, seed: int, batches: Sequence[dict], opt: dict,
                    prec: str = "f32") -> dict:
    """AdamW over ``batches`` (each {"tokens", "labels", "docs"}) from the
    seeded weights, as the configuration states it, in float32.

    Returns {"loss": [per step], "grad": per-leaf norms of the first
    step's clipped gradient, "change": per-leaf norms of the parameters'
    change after the last step}. The clipped gradient of each earlier step
    is kept on the host and brought back one leaf at a time, so only the
    parameters, one gradient and one leaf's moments are on the device."""
    p = flat_params(m, seed)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    grad_fns = {}
    history: List[Dict[str, np.ndarray]] = []
    out = {"loss": []}

    @jax.jit
    def update(pk, gk, prev, lr, t):
        """One leaf: m and v rebuilt from this step's and the kept
        gradients (oldest first), then the AdamW step."""
        m_, v_ = jnp.zeros_like(gk), jnp.zeros_like(gk)
        for g in list(prev) + [gk]:
            m_ = b1 * m_ + (1 - b1) * g
            v_ = b2 * v_ + (1 - b2) * g * g
        u = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
        return pk - lr * u

    for step, batch in enumerate(batches):
        docs = tuple(int(n) for n in batch["docs"])
        if docs not in grad_fns:
            grad_fns[docs] = jax.jit(jax.value_and_grad(
                functools.partial(train_loss, m=m, prec=prec, docs=docs)))
        loss, g = grad_fns[docs](p, jnp.asarray(batch["tokens"]),
                                 jnp.asarray(batch["labels"]))
        out["loss"].append(float(loss))
        gnorm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                   for x in g.values())))
        scale = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12))
        g = {k: x * scale for k, x in g.items()}
        if step == 0:
            out["grad"] = leaf_norms(g)
            out["grad_norm"] = gnorm
        lr = schedule(opt, step)
        t = float(step + 1)
        for key in sorted(p):
            prev = [jnp.asarray(h[key]) for h in history]
            new = update(p[key], g[key], prev, lr, t)
            if decays(key):
                new = new - lr * opt["weight_decay"] * p[key]
            p[key] = new
            del prev
        if step + 1 < len(batches):
            history.append({k: np.asarray(jax.device_get(x))
                            for k, x in g.items()})
        del g
    p0 = flat_params(m, seed)
    out["change"] = leaf_norms({k: p[k] - p0[k] for k in p})
    return out


def schedule(opt: dict, step: int) -> float:
    """The learning rate of step ``step`` (0-based): linear warm-up, then
    cosine decay to ``min_lr_frac`` of the peak."""
    s = step + 1.0
    if s < opt["warmup_steps"]:
        return opt["lr"] * s / max(opt["warmup_steps"], 1)
    t = min(max((s - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["lr"] * (opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5
                        * (1 + math.cos(math.pi * t)))
