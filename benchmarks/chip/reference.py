"""Plain reference of the served models.

A Qwen3 decoder (pre-norm RMSNorm blocks, grouped-query attention with
per-head q/k RMSNorm and rotary embeddings, gated SiLU MLP, untied head)
written from the published architecture in straightforward ``jax.numpy``:
one sequence at a time, full causal attention, no cache, no batching, no
kernels.  Every operation computes in float32, and every matrix product
runs at ``Precision.HIGHEST``.  It imports nothing of the program under
test; it reads the weights that ``weights.py`` made from the seed.

A LUT-MU MLP is computed by its definition: walk each codebook's tree
from the root (go right where the split value is >= the node's
threshold), sum the chosen int8 table rows exactly in int32, then apply
the column scale and offset.  With pruning on, the down tree's split
values are the gate/up package in its level-major order.

``numerics`` says where values are rounded:

* ``"f32"``: nowhere.  This is the reference the check compares with.
* ``"bf16"``: every operand of every product and every tree's input
  rounded to bfloat16, the configuration's own precision: a second
  witness of how far the configuration's precision alone moves the
  tokens of a chaotic LUT-MU model.
* ``"fp8"``: the control.  Every operand of every product and every
  tree's input rounded to float8_e4m3fn, and the int8 tables cut to 16
  levels (int4): the step to the next lower precision, which the
  comparison has to reject.

Departures from the published model, each one shared with the program:
norm weights are stored as their offset from 1; the MLPs may be LUT-MU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.spec import ModelSpec

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
HEAD_BLOCKS = 8
NUMERICS = ("f32", "bf16", "fp8")


def _to(x, dtype):
    if dtype is None:
        return x.astype(F32)
    if dtype == FP8:
        x = jnp.clip(x, -FP8_MAX, FP8_MAX)
    return x.astype(dtype).astype(F32)


class _N:
    """Rounding of one numerics mode, applied to operands (``op``)."""

    def __init__(self, numerics: str):
        if numerics not in NUMERICS:
            raise ValueError(f"numerics {numerics!r} not in {NUMERICS}")
        self.operand = {"f32": None, "bf16": jnp.bfloat16,
                        "fp8": FP8}[numerics]
        self.int4 = numerics == "fp8"

    def op(self, x):
        return _to(x, self.operand)

    def mm(self, a, b):
        return jnp.matmul(self.op(a), self.op(b), precision=HIGHEST)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(F32))


def _rope(x, pos, theta):
    """x (T, H, hd); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(0, half, dtype=F32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(F32) * freqs                    # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _encode(xs, thresholds, depth):
    """xs (T, C, depth) split values -> (T, C) leaf ids by a tree walk."""
    node = jnp.zeros(xs.shape[:2], jnp.int32)
    for level in range(depth):
        thr = jnp.take_along_axis(thresholds[None], node[..., None], 2)[..., 0]
        node = 2 * node + 1 + (xs[:, :, level] >= thr).astype(jnp.int32)
    return node - (2 ** depth - 1)


def _table_sum(leaf, lut, scale, offset, n: _N):
    """Exact sum over codebooks of each row's chosen table rows."""
    c, g, cols = lut.shape
    if n.int4:                  # int8 tables -> 16 levels (int4)
        lut = (jnp.round((lut.astype(F32) + 128.0) / 17.0) * 17.0
               - 128.0).astype(jnp.int8)
    onehot = jax.nn.one_hot(leaf, g, dtype=jnp.int8).reshape(leaf.shape[0], -1)
    acc = jax.lax.dot_general(onehot, lut.reshape(c * g, cols),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(F32) * scale + offset


def _lutmu(p, x, spec: ModelSpec, n: _N):
    t = x.shape[0]
    x = n.op(x)
    xs = jnp.take_along_axis(x.reshape(t, spec.c_up, spec.d_sub),
                             p["up_split_dims"][None], axis=2)
    leaf = _encode(xs, p["up_thresholds"], spec.depth)
    gate = _table_sum(leaf, p["lut_gate"], p["lut_gate_scale"],
                      p["lut_gate_offset"], n)
    up = _table_sum(leaf, p["lut_up"], p["lut_up_scale"], p["lut_up_offset"],
                    n)
    h = n.op(jax.nn.silu(gate) * up)
    if spec.prune:      # level-major package -> (T, C_down, depth)
        hs = h.reshape(t, spec.depth, spec.c_down).transpose(0, 2, 1)
    else:
        hs = jnp.take_along_axis(h.reshape(t, spec.c_down, spec.d_sub),
                                 p["down_split_dims"][None], axis=2)
    leaf = _encode(hs, p["down_thresholds"], spec.depth)
    return _table_sum(leaf, p["lut_down"], p["lut_down_scale"],
                      p["lut_down_offset"], n)


def _attention(p, x, spec: ModelSpec, n: _N):
    t = x.shape[0]
    nq, nkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    pos = jnp.arange(t)
    q = n.mm(x, p["wq"]).reshape(t, nq, hd)
    k = n.mm(x, p["wk"]).reshape(t, nkv, hd)
    v = n.mm(x, p["wv"]).reshape(t, nkv, hd)
    q = _rope(_rms(q, p["q_norm"], spec.norm_eps), pos, spec.rope_theta)
    k = _rope(_rms(k, p["k_norm"], spec.norm_eps), pos, spec.rope_theta)
    k = jnp.repeat(k, nq // nkv, axis=1)      # head h reads kv head h // g
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("thd,uhd->htu", n.op(q), n.op(k), precision=HIGHEST)
    s = s * np.float32(1.0 / np.sqrt(hd))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("htu,uhd->thd", n.op(w), n.op(v), precision=HIGHEST)
    return n.mm(o.reshape(t, nq * hd), p["wo"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(layers, i, h, spec: ModelSpec, numerics: str):
    n = _N(numerics)
    p = jax.tree.map(lambda a: a[i], layers)
    h = h + _attention(p["attn"], _rms(h, p["ln1"], spec.norm_eps), spec, n)
    x = _rms(h, p["ln2"], spec.norm_eps)
    if "amm_mlp" in p:
        out = _lutmu(p["amm_mlp"], x, spec, n)
    else:
        m = p["mlp"]
        out = n.mm(jax.nn.silu(n.mm(x, m["w_gate"])) * n.mm(x, m["w_up"]),
                   m["w_down"])
    return h + out


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(params, h, rows, targets, spec: ModelSpec, numerics: str):
    """Max, argmax and the logits at ``targets`` (K, R) of rows ``rows``
    of the final hidden states, a block of the vocabulary at a time."""
    n = _N(numerics)
    x = _rms(h[rows], params["final_norm"], spec.norm_eps)
    blk = spec.vocab // HEAD_BLOCKS

    def block(b):
        w = jax.lax.dynamic_slice_in_dim(params["lm_head"], b * blk, blk, 1)
        z = n.mm(x, w)                                          # (R, blk)
        local = targets - b * blk
        inside = (local >= 0) & (local < blk)
        at = jnp.take_along_axis(z[None], jnp.clip(local, 0, blk - 1)[..., None],
                                 2)[..., 0]
        return z.max(-1), z.argmax(-1) + b * blk, jnp.where(inside, at, -jnp.inf)

    mx, am, at = jax.lax.map(block, jnp.arange(HEAD_BLOCKS))
    best = mx.argmax(0)      # first block holding the max: the first index
    return (jnp.take_along_axis(mx, best[None], 0)[0],
            jnp.take_along_axis(am, best[None], 0)[0], at.max(0))


def score(params, spec: ModelSpec, tokens, first: int, targets,
          numerics: str = "f32"):
    """Run ``tokens`` (a prompt of ``first + 1`` tokens and the tokens
    served after it, all but the last) and read the logits of positions
    ``first`` onward.

    ``targets`` is (K, R) token ids, R = len(tokens) - first.  Returns
    numpy (max logit (R,), argmax (R,), logit at each target (K, R)).
    The sequence is padded to ``spec.max_len`` and the rows to a multiple
    of 256, so every call runs the same programs."""
    t = len(tokens)
    if t > spec.max_len or spec.vocab % HEAD_BLOCKS:
        raise ValueError(f"{t} tokens, max_len {spec.max_len}, vocab "
                         f"{spec.vocab}")
    ids = np.zeros(spec.max_len, np.int32)
    ids[:t] = tokens
    h = params["embed"][jnp.asarray(ids)].astype(F32)
    for i in range(spec.layers):
        h = _layer(params["layers"], jnp.int32(i), h, spec, numerics)
    r = t - first
    pad = -(-r // 256) * 256
    rows = np.zeros(pad, np.int32)
    rows[:r] = np.arange(first, t)
    tg = np.zeros((targets.shape[0], pad), np.int32)
    tg[:, :r] = targets
    mx, am, at = _head(params, h, jnp.asarray(rows), jnp.asarray(tg), spec,
                       numerics)
    return np.asarray(mx)[:r], np.asarray(am)[:r], np.asarray(at)[:, :r]
