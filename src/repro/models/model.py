"""Model assembly: embeddings → layer stacks (scan) → head, plus the
prefill / decode serving paths with KV / SSM caches.

One code path covers all 10 assigned architectures:

  * uniform stacks (dense / MoE / SSM / VLM-backbone) are a single
    ``lax.scan`` over stacked per-layer params with per-layer *flag arrays*
    (sliding-window size, 0 ⇒ global) — keeps the HLO one-block small, which
    is what makes the 62-layer 512-device dry-runs compile quickly;
  * Jamba's 1:7 attention:Mamba interleave with MoE-every-2 scans over
    period-8 super-blocks whose 8 positions have their own stacked params;
  * Whisper adds a bidirectional encoder stack and cross-attention in the
    decoder;
  * InternVL prepends stubbed patch embeddings to the token stream.

``constrain(x, kind)`` is the sharding hook — identity on CPU, a
``with_sharding_constraint`` closure under the production mesh.
"""
from __future__ import annotations

import os
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba as MB
from repro.models import moe as MOE
from repro.models import amm_mlp as AMM
from repro.models.config import ModelConfig

Array = jax.Array
Constrain = Callable[[Array, str], Array]
_id: Constrain = lambda x, kind: x

_GLOBAL_WINDOW = np.int32(2**30)  # "no window" sentinel for flag arrays

# Speculative-verify window implementations (see paged_verify_step):
# "scan" replays one exact paged_decode_step per window position (the
# differential oracle); "fused" is the layer-major one-gather-per-layer
# restructure backed by kernels/fused_verify.py.  Both are bit-identical
# on greedy streams — the suites in tests/test_speculative.py pin it.
VERIFY_BACKENDS = ("scan", "fused")


def resolve_verify_backend(backend: str = "auto") -> str:
    """``auto`` → ``$REPRO_VERIFY_BACKEND`` if set, else ``fused``."""
    if backend == "auto":
        backend = os.environ.get("REPRO_VERIFY_BACKEND", "auto")
    if backend == "auto":
        backend = "fused"
    if backend not in VERIFY_BACKENDS:
        raise ValueError(
            f"verify backend must be 'auto' or one of {VERIFY_BACKENDS}, "
            f"got {backend!r}")
    return backend


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(cfg: ModelConfig, key, layer_idx: int, dtype,
                serving: bool = False) -> dict:
    """One decoder block's params.  ``layer_idx`` decides attn/mamba/moe."""
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((d,), dtype)}
    if cfg.layer_is_attn(layer_idx):
        p["attn"] = A.init_attn_params(cfg, ks[0], dtype)
    else:
        p["mamba"] = MB.init_mamba_params(cfg, ks[0], dtype)
    if cfg.family == "ssm":
        return p  # mamba2: single mixer sub-block, no MLP
    p["ln2"] = jnp.zeros((d,), dtype)
    if cfg.layer_is_moe(layer_idx):
        p["moe"] = MOE.init_moe_params(cfg, ks[1], dtype)
    elif serving and cfg.amm.enabled and "mlp" in cfg.amm.targets:
        p["amm_mlp"] = AMM.init_amm_mlp_params(cfg, ks[1])
    else:
        p["mlp"] = {
            "w_gate": L.dense_init(ks[1], d, cfg.d_ff, dtype),
            "w_up": L.dense_init(ks[2], d, cfg.d_ff, dtype),
            "w_down": L.dense_init(ks[3], cfg.d_ff, d, dtype),
        }
    return p


def _init_encoder_block(cfg: ModelConfig, key, dtype) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "ln1": jnp.zeros((d,), dtype),
        "attn": A.init_attn_params(cfg, ks[0], dtype),
        "ln2": jnp.zeros((d,), dtype),
        "mlp": {
            "w_gate": L.dense_init(ks[1], d, cfg.d_ff, dtype),
            "w_up": L.dense_init(ks[2], d, cfg.d_ff, dtype),
            "w_down": L.dense_init(ks[3], cfg.d_ff, d, dtype),
        },
    }


def _init_decdec_block(cfg: ModelConfig, key, idx: int, dtype) -> dict:
    """Whisper decoder block: self-attn + cross-attn + mlp."""
    p = _init_block(cfg, key, idx, dtype)
    k2 = jax.random.fold_in(key, 17)
    p["ln_cross"] = jnp.zeros((cfg.d_model,), dtype)
    p["cross"] = A.init_cross_attn_params(cfg, k2, dtype)
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32,
                serving: bool = False) -> dict:
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    params = {
        "embed": L.embed_init(keys[0], cfg.vocab_size, d, dtype),
        "final_norm": jnp.zeros((d,), dtype),
        "lm_head": L.dense_init(keys[1], d, cfg.vocab_size, dtype),
    }

    if cfg.is_hybrid:
        period = cfg.attn_every
        n_groups = cfg.num_layers // period
        layer_groups = {}
        for pos in range(period):
            pks = jax.random.split(jax.random.fold_in(keys[2], pos), n_groups)
            layer_groups[f"pos{pos}"] = jax.vmap(
                lambda k: _init_block(cfg, k, pos, dtype, serving))(pks)
        params["layers"] = layer_groups
    elif cfg.is_encdec:
        eks = jax.random.split(keys[2], cfg.encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _init_encoder_block(cfg, k, dtype))(eks),
            "pos_embed": L.embed_init(keys[3], cfg.num_frontend_tokens, d, dtype),
            "final_norm": jnp.zeros((d,), dtype),
        }
        dks = jax.random.split(keys[4], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_decdec_block(cfg, k, 0, dtype))(dks)
        params["pos_embed"] = L.embed_init(keys[5], cfg.max_seq_len, d, dtype)
    else:
        lks = jax.random.split(keys[2], cfg.num_layers)
        # uniform structure across layers (verified by config properties)
        params["layers"] = jax.vmap(
            lambda k: _init_block(cfg, k, cfg.moe_offset, dtype, serving))(lks)
    return params


def window_flags(cfg: ModelConfig) -> Array:
    """(L,) per-layer effective attention window (sentinel = global)."""
    wins = []
    for i in range(cfg.num_layers):
        if cfg.sliding_window is not None and cfg.layer_is_local(i):
            wins.append(cfg.sliding_window)
        else:
            wins.append(int(_GLOBAL_WINDOW))
    return jnp.asarray(wins, jnp.int32)


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------


def _block_apply(cfg: ModelConfig, lp: dict, h: Array, positions: Array,
                 window, constrain: Constrain, layer_idx: int,
                 mlp_tap=None) -> Array:
    if "mamba" in lp:
        h = h + MB.mamba_forward(
            lp["mamba"], L.rms_norm(h, lp["ln1"], cfg.norm_eps), cfg,
            constrain=constrain)
        if "ln2" not in lp:
            return constrain(h, "activation")
    else:
        a_out = A.attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                            cfg, positions=positions, window=window,
                            constrain=constrain)
        h = constrain(h + a_out, "activation")
    if "ln_cross" in lp:
        return h  # cross-attention handled by the enc-dec wrapper
    mlp_in = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
    if mlp_tap is not None:
        mlp_tap(layer_idx, mlp_in)
    if "moe" in lp:
        out = MOE.moe_apply(lp["moe"], mlp_in, cfg, constrain)
    elif "amm_mlp" in lp:
        out = AMM.amm_mlp_apply(lp["amm_mlp"], mlp_in, cfg, constrain)
    else:
        m = lp["mlp"]
        out = L.gated_mlp(mlp_in, m["w_gate"].astype(h.dtype),
                          m["w_up"].astype(h.dtype),
                          m["w_down"].astype(h.dtype), cfg.act)
    return constrain(h + out, "activation")


def _run_uniform_stack(cfg: ModelConfig, layers: dict, h: Array,
                       positions: Array, constrain: Constrain,
                       remat: bool) -> Array:
    windows = window_flags(cfg)

    def body(carry, xs):
        lp, win = xs
        return _block_apply(cfg, lp, carry, positions, win, constrain, 0), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, (layers, windows))
    return h


def capture_mlp_inputs(params: dict, tokens: Array, cfg: ModelConfig, *,
                       compute_dtype=jnp.float32) -> list:
    """Run the forward pass unrolled, recording each layer's MLP input.

    The offline compiler's calibration hook: the returned ``(B·S, D)``
    activations (one per layer, in layer order) are exactly what the
    serving-time AMM-MLP substitution will see as its input distribution.
    Uniform (non-hybrid, non-enc-dec) attention stacks only — the families
    the AMM-MLP substitution targets.
    """
    if cfg.is_hybrid or cfg.is_encdec or cfg.family == "ssm":
        raise ValueError(
            f"MLP-input capture supports uniform attention stacks, "
            f"not family {cfg.family!r}")
    cd = compute_dtype
    b, s = tokens.shape
    h = params["embed"].astype(cd)[tokens]
    windows = window_flags(cfg)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    captured: list = []

    def tap(layer_idx, mlp_in):
        del layer_idx  # python-unrolled: append order is layer order
        captured.append(mlp_in.reshape(-1, cfg.d_model))

    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = _block_apply(cfg, lp, h, positions, windows[l], _id, l,
                         mlp_tap=tap)
    return captured


def _run_hybrid_stack(cfg: ModelConfig, layers: dict, h: Array,
                      positions: Array, constrain: Constrain,
                      remat: bool) -> Array:
    period = cfg.attn_every

    def one(hh, lp, pos):
        return _block_apply(cfg, lp, hh, positions, _GLOBAL_WINDOW,
                            constrain, pos)

    def body(carry, xs):
        hh = carry
        for pos in range(period):
            # per-layer remat *inside* the super-block: without it the
            # group's vjp holds 8 layers of SSD residuals simultaneously
            # (hundreds of GiB at Jamba scale).
            fn = (jax.checkpoint(one, static_argnums=(2,),
                                 policy=jax.checkpoint_policies.nothing_saveable)
                  if remat else one)
            hh = fn(hh, xs[f"pos{pos}"], pos)
        return hh, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, layers)
    return h


def _run_encoder(cfg: ModelConfig, enc_params: dict, frames: Array,
                 constrain: Constrain, remat: bool) -> Array:
    t = frames.shape[1]
    h = frames + enc_params["pos_embed"][:t].astype(frames.dtype)

    def body(carry, lp):
        hh = carry
        a_out = A.attention(lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                            cfg, positions=jnp.arange(t)[None], causal=False,
                            window=None, constrain=constrain)
        hh = hh + a_out
        m = lp["mlp"]
        out = L.gated_mlp(L.rms_norm(hh, lp["ln2"], cfg.norm_eps),
                          m["w_gate"].astype(hh.dtype),
                          m["w_up"].astype(hh.dtype),
                          m["w_down"].astype(hh.dtype), cfg.act)
        return constrain(hh + out, "activation"), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, enc_params["layers"])
    return L.rms_norm(h, enc_params["final_norm"], cfg.norm_eps)


def _run_encdec_decoder(cfg: ModelConfig, layers: dict, h: Array,
                        enc: Array, positions: Array, constrain: Constrain,
                        remat: bool) -> Array:
    def body(carry, lp):
        hh = carry
        a_out = A.attention(lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                            cfg, positions=positions, window=None,
                            constrain=constrain)
        hh = hh + a_out
        c_out = A.cross_attention(lp["cross"],
                                  L.rms_norm(hh, lp["ln_cross"], cfg.norm_eps),
                                  enc, cfg, constrain=constrain)
        hh = hh + c_out
        m = lp["mlp"]
        out = L.gated_mlp(L.rms_norm(hh, lp["ln2"], cfg.norm_eps),
                          m["w_gate"].astype(hh.dtype),
                          m["w_up"].astype(hh.dtype),
                          m["w_down"].astype(hh.dtype), cfg.act)
        return constrain(hh + out, "activation"), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, layers)
    return h


def forward(params: dict, tokens: Array, cfg: ModelConfig, *,
            constrain: Constrain = _id,
            extra_embeds: Optional[Array] = None,
            remat: bool = True,
            compute_dtype=jnp.bfloat16) -> Array:
    """tokens (B, S) [+ optional frontend embeds (B, T, D)] → logits f32.

    For enc-dec (Whisper) ``extra_embeds`` are the encoder's input frames;
    for VLM they are patch embeddings prepended to the token stream.
    """
    cd = compute_dtype
    b, s = tokens.shape
    h = params["embed"].astype(cd)[tokens]
    h = constrain(h, "activation")

    if cfg.is_encdec:
        assert extra_embeds is not None, "whisper needs frame embeddings"
        enc = _run_encoder(cfg, params["encoder"], extra_embeds.astype(cd),
                           constrain, remat)
        h = h + params["pos_embed"][:s].astype(cd)
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        h = _run_encdec_decoder(cfg, params["layers"], h, enc, positions,
                                constrain, remat)
    else:
        if extra_embeds is not None:  # VLM: prepend patch embeddings
            h = jnp.concatenate([extra_embeds.astype(cd), h], axis=1)
        s_tot = h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s_tot), (b, s_tot))
        if cfg.is_hybrid:
            h = _run_hybrid_stack(cfg, params["layers"], h, positions,
                                  constrain, remat)
        else:
            h = _run_uniform_stack(cfg, params["layers"], h, positions,
                                   constrain, remat)
        if extra_embeds is not None:
            h = h[:, extra_embeds.shape[1]:]  # logits over text positions only

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = h @ params["lm_head"].astype(cd)
    return constrain(logits.astype(jnp.float32), "logits")


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------


def supports_paged(cfg: ModelConfig) -> bool:
    """Families with a paged KV decode path: uniform attention stacks
    (dense / MoE / VLM backbones).  SSM and hybrid caches are recurrent
    state (nothing to page); enc-dec keeps its cross-attention cache
    per-slot.  Those families serve through the fixed-slot engine."""
    return not (cfg.family == "ssm" or cfg.is_hybrid or cfg.is_encdec)


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16) -> dict:
    """Physical page pool: ``(L, P, page_size, n_kv, hd)`` per k/v.  The
    caller (``serving/kv_cache.py``) includes its trash page in ``P``.
    Every paged program carries the whole pool through its layer scan and
    writes each token in place (:func:`_scan_paged_layers`), so a donated
    pool is never copied."""
    if not supports_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged KV layout")
    hd = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads
    shape = (cfg.num_layers, num_pages, page_size, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _scan_paged_layers(layer_fn, h: Array, params: dict, cache: dict,
                       cfg: ModelConfig) -> Tuple[Array, dict]:
    """Run ``layer_fn(hh, lp, k_pool, v_pool, layer, win) -> (hh, (k_pool,
    v_pool))`` over the layer stack, with the K and V pools in the scan's
    carry; returns the last hidden state and the updated cache.

    The pools are carried, not scanned as ``xs``/``ys``: with ``ys`` XLA
    builds a fresh output pool (a broadcast of zeros and a copy), writes
    each layer's whole slice into it and copies the result out of the
    loop — three passes over the whole pool in every program, even with
    the pool donated.  Carried, each layer writes only its new tokens'
    rows in place (``pool.at[layer, phys, off]``) and gathers its view
    straight out of the pool (``attention._paged_view``).
    """
    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def body(carry, xs):
        hh, kp, vp = carry
        lp, win, layer = xs
        hh, (kp, vp) = layer_fn(hh, lp, kp, vp, layer, win)
        return (hh, kp, vp), None

    (h, kp, vp), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["layers"], window_flags(cfg), layers))
    return h, dict(cache, k=kp, v=vp)


def _mlp_out(lp: dict, mlp_in: Array, cfg: ModelConfig, constrain: Constrain,
             cd) -> Array:
    """The per-block MLP dispatch shared by every serving path (dense /
    MoE / LUT-MU) — one definition so slot and paged decode cannot drift."""
    if "moe" in lp:
        return MOE.moe_apply(lp["moe"], mlp_in, cfg, constrain)
    if "amm_mlp" in lp:
        return AMM.amm_mlp_apply(lp["amm_mlp"], mlp_in, cfg, constrain)
    m = lp["mlp"]
    return L.gated_mlp(mlp_in, m["w_gate"].astype(cd), m["w_up"].astype(cd),
                       m["w_down"].astype(cd), cfg.act)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    hd = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads

    def attn_cache(n_layers):
        return {
            "k": jnp.zeros((n_layers, batch, max_len, nkv, hd), dtype),
            "v": jnp.zeros((n_layers, batch, max_len, nkv, hd), dtype),
        }

    if cfg.family == "ssm":
        mc = MB.init_mamba_cache(cfg, batch, dtype)
        return {"mamba": jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape).copy(),
            mc)}
    if cfg.is_hybrid:
        period = cfg.attn_every
        n_groups = cfg.num_layers // period
        cache = {}
        for pos in range(period):
            if cfg.layer_is_attn(pos):
                cache[f"pos{pos}"] = attn_cache(n_groups)
            else:
                mc = MB.init_mamba_cache(cfg, batch, dtype)
                cache[f"pos{pos}"] = {"mamba": jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape).copy(),
                    mc)}
        return cache
    if cfg.is_encdec:
        c = attn_cache(cfg.num_layers)
        c["cross_k"] = jnp.zeros(
            (cfg.num_layers, batch, cfg.num_frontend_tokens, nkv, hd), dtype)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
        c["enc"] = jnp.zeros((batch, cfg.num_frontend_tokens, cfg.d_model), dtype)
        return c
    return attn_cache(cfg.num_layers)


def decode_step(params: dict, token: Array, pos: Array, cache: dict,
                cfg: ModelConfig, *, constrain: Constrain = _id,
                compute_dtype=jnp.bfloat16) -> Tuple[Array, dict]:
    """One decode step for every architecture family.

    token: (B, 1) int32; pos: scalar int32, or a (B,) vector of per-row
    positions (tokens so far) so continuous-batching slots admitted at
    different times decode at their own offsets.
    Returns (logits (B, 1, V) f32, updated cache).
    """
    cd = compute_dtype
    b = token.shape[0]
    h = params["embed"].astype(cd)[token]  # (B, 1, D)
    windows = window_flags(cfg)

    if cfg.family == "ssm":
        def body(carry, xs):
            hh = carry
            lp, mc = xs
            out, new_mc = MB.mamba_decode_step(
                lp["mamba"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg, mc)
            return hh + out, new_mc

        h, new_m = jax.lax.scan(body, h, (params["layers"], cache["mamba"]))
        new_cache = {"mamba": new_m}

    elif cfg.is_hybrid:
        new_cache = {}
        period = cfg.attn_every
        hh = h
        groups = params["layers"]

        def body(carry, xs):
            hh = carry
            lps, caches = xs
            new_caches = {}
            for p_ in range(period):
                lp = lps[f"pos{p_}"]
                cc = caches[f"pos{p_}"]
                if "mamba" in lp:
                    out, nc = MB.mamba_decode_step(
                        lp["mamba"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                        cfg, cc["mamba"])
                    hh = hh + out
                    new_caches[f"pos{p_}"] = {"mamba": nc}
                else:
                    out, (nk, nv) = A.decode_step(
                        lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                        cfg, cc["k"], cc["v"], pos, None)
                    hh = hh + out
                    new_caches[f"pos{p_}"] = {"k": nk, "v": nv}
                mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
                if "moe" in lp:
                    out = MOE.moe_apply(lp["moe"], mlp_in, cfg, constrain)
                elif "amm_mlp" in lp:
                    out = AMM.amm_mlp_apply(lp["amm_mlp"], mlp_in, cfg, constrain)
                else:
                    m = lp["mlp"]
                    out = L.gated_mlp(mlp_in, m["w_gate"].astype(cd),
                                      m["w_up"].astype(cd),
                                      m["w_down"].astype(cd), cfg.act)
                hh = hh + out
            return hh, new_caches

        h, new_cache = jax.lax.scan(body, hh, (groups, cache))

    elif cfg.is_encdec:
        # learned decoder positional embedding at each row's position
        pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
        pe = jnp.take(params["pos_embed"], pos_b, axis=0)[:, None]
        h = h + pe.astype(cd)

        def body(carry, xs):
            hh = carry
            lp, ck, cv, xk, xv = xs
            out, (nk, nv) = A.decode_step(
                lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
                ck, cv, pos, None)
            hh = hh + out
            # cross-attention against the cached encoder K/V
            qx = L.rms_norm(hh, lp["ln_cross"], cfg.norm_eps)
            hd = cfg.resolved_head_dim
            nq, nkv = cfg.num_heads, cfg.num_kv_heads
            q = (qx @ lp["cross"]["wq"].astype(cd)).reshape(b, 1, nq, hd)
            qg = A._grouped(q, nkv)
            scale = 1.0 / np.sqrt(hd)
            lg = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32),
                            xk.astype(jnp.float32)) * scale
            w = jax.nn.softmax(lg, axis=-1)
            c_out = jnp.einsum("bngst,btnh->bsngh", w, xv.astype(jnp.float32))
            c_out = c_out.reshape(b, 1, nq * hd).astype(cd) @ lp["cross"]["wo"].astype(cd)
            hh = hh + c_out
            m = lp["mlp"]
            out = L.gated_mlp(L.rms_norm(hh, lp["ln2"], cfg.norm_eps),
                              m["w_gate"].astype(cd), m["w_up"].astype(cd),
                              m["w_down"].astype(cd), cfg.act)
            return hh + out, (nk, nv)

        h, (nk, nv) = jax.lax.scan(
            body, h, (params["layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=nk, v=nv)

    else:
        def body(carry, xs):
            hh = carry
            lp, ck, cv, win = xs
            out, (nk, nv) = A.decode_step(
                lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
                ck, cv, pos, win)
            hh = constrain(hh + out, "activation")
            mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
            hh = constrain(hh + _mlp_out(lp, mlp_in, cfg, constrain, cd),
                           "activation")
            return hh, (nk, nv)

        h, (nk, nv) = jax.lax.scan(
            body, h, (params["layers"], cache["k"], cache["v"], windows))
        new_cache = dict(cache, k=nk, v=nv)

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"].astype(cd)).astype(jnp.float32)
    return constrain(logits, "logits"), new_cache


def paged_decode_step(params: dict, token: Array, pos: Array,
                      page_table: Array, cache: dict, cfg: ModelConfig, *,
                      constrain: Constrain = _id,
                      compute_dtype=jnp.bfloat16,
                      write_ok: Optional[Array] = None) -> Tuple[Array, dict]:
    """One decode step against the paged KV cache (uniform attention
    stacks only — see :func:`supports_paged`).

    token: (B, 1) int32; pos: (B,) int32 per-row write positions;
    page_table: (B, max_pages) int32 logical→physical page map (rows with
    no active request point entirely at the trash page); cache:
    ``{"k","v"}`` of (L, P, page_size, n_kv, hd); write_ok: optional (B,)
    bool — rows with False scatter their K/V to the trash page (the
    speculative loops' out-of-window guard; see
    ``attention.paged_decode_step``).

    The per-block math is the same ``rms → attn → rms → mlp`` pipeline as
    :func:`decode_step`'s uniform branch (attention reads through the
    shared ``_decode_attend``), so token streams are bit-identical to the
    slot cache — the contract the differential tests pin down.  The pools
    ride the layer scan's carry and each layer writes its new rows in
    place (:func:`_scan_paged_layers`).
    """
    if not supports_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged decode path")
    cd = compute_dtype
    h = params["embed"].astype(cd)[token]  # (B, 1, D)

    def layer(hh, lp, kp, vp, i, win):
        out, pools = A.paged_decode_step(
            lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
            kp, vp, i, page_table, pos, win, write_ok=write_ok)
        hh = constrain(hh + out, "activation")
        mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
        hh = constrain(hh + _mlp_out(lp, mlp_in, cfg, constrain, cd),
                       "activation")
        return hh, pools

    h, cache = _scan_paged_layers(layer, h, params, cache, cfg)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"].astype(cd)).astype(jnp.float32)
    return constrain(logits, "logits"), cache


def paged_verify_step(params: dict, tokens: Array, pos: Array,
                      n_valid: Array, page_table: Array, cache: dict,
                      cfg: ModelConfig, *, constrain: Constrain = _id,
                      compute_dtype=jnp.bfloat16,
                      backend: str = "auto") -> Tuple[Array, dict]:
    """Multi-token target step: per-position logits for a whole verify
    window in **one** compiled program.

    The speculative-decoding verifier: row ``b`` feeds ``tokens[b]``
    (its last emitted token followed by the draft proposals) at cache
    positions ``pos[b] .. pos[b]+W-1`` and gets back the greedy target's
    logits after every prefix.  tokens: (B, W) int32; pos: (B,) int32
    start positions; n_valid: (B,) int32 — tokens past a row's window
    scatter to the trash page and their logits are don't-cares.

    Returns ``(logits (B, W, V) f32, updated cache)`` where
    ``argmax(logits[b, j])`` is the token the target would emit after
    ``tokens[b, :j+1]``.

    Two implementations, selected by ``backend`` (``auto`` honours
    ``$REPRO_VERIFY_BACKEND``, then defaults to ``fused``):

    * ``scan`` — the differential oracle: a ``lax.scan`` of the **exact**
      :func:`paged_decode_step` computation, one window position at a
      time.  Bit-exactness to plain decode is trivially structural, but
      every layer re-gathers its page view W times.
    * ``fused`` — the layer-major restructure
      (:func:`attention.paged_verify_window` +
      ``kernels/fused_verify.py``): per layer the page view is gathered
      once and all W positions attend against it, each under its own
      causal mask, with every matmul still issued at the oracle's
      per-token shapes.  Token ``j``'s layer-``l`` K/V depends only on
      its layer-``l-1`` hidden state, so swapping the loop nest from
      token-major to layer-major changes no value — the differential
      suites in ``tests/test_speculative.py`` pin the two backends
      bit-identical.

    A W-wide masked softmax would be mathematically identical but not
    *bitwise* identical (different reduction shapes); both backends
    therefore keep W one-token-shaped reads — the fused one just stops
    paying the gather W times.
    """
    backend = resolve_verify_backend(backend)
    if backend == "fused":
        return _paged_verify_step_fused(
            params, tokens, pos, n_valid, page_table, cache, cfg,
            constrain=constrain, compute_dtype=compute_dtype)
    w = tokens.shape[1]

    def body(cache, xs):
        tok, off = xs  # tok: (B,), off: scalar step index
        logits, cache = paged_decode_step(
            params, tok[:, None], pos + off, page_table, cache, cfg,
            constrain=constrain, compute_dtype=compute_dtype,
            write_ok=off < n_valid)
        return cache, logits[:, 0]

    cache, logits = jax.lax.scan(
        body, cache, (tokens.T, jnp.arange(w, dtype=jnp.int32)))
    return jnp.swapaxes(logits, 0, 1), cache  # (B, W, V)


def _paged_verify_step_fused(params: dict, tokens: Array, pos: Array,
                             n_valid: Array, page_table: Array, cache: dict,
                             cfg: ModelConfig, *, constrain: Constrain = _id,
                             compute_dtype=jnp.bfloat16) -> Tuple[Array, dict]:
    """Layer-major fused verify window (see :func:`paged_verify_step`).

    Outer scan over layers (the pools carried and written in place, see
    :func:`_scan_paged_layers`), ``attention.paged_verify_window`` per
    layer (one page gather, W masked attends, per-token projections), then
    per-token head matmuls — bit-identical to the ``scan`` oracle at
    every in-window position.
    """
    if not supports_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged decode path")
    cd = compute_dtype
    b, w = tokens.shape
    h = params["embed"].astype(cd)[tokens]  # (B, W, D)

    def layer(hh, lp, kp, vp, i, win):
        out, pools = A.paged_verify_window(
            lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
            kp, vp, i, page_table, pos, n_valid, win)
        hh = constrain(hh + out, "activation")
        mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)

        def mlp_tok(_, mj):  # (B, D) — the oracle's (B, 1, D) MLP shapes
            return None, _mlp_out(lp, mj[:, None], cfg, constrain, cd)[:, 0]

        _, mo = jax.lax.scan(mlp_tok, None, jnp.swapaxes(mlp_in, 0, 1))
        hh = constrain(hh + jnp.swapaxes(mo, 0, 1), "activation")
        return hh, pools

    h, cache = _scan_paged_layers(layer, h, params, cache, cfg)
    hn = L.rms_norm(h, params["final_norm"], cfg.norm_eps)

    def head_tok(_, hj):  # (B, D) — the oracle's (B, 1, D) head matmul
        logits = (hj[:, None] @ params["lm_head"].astype(cd)
                  ).astype(jnp.float32)
        return None, constrain(logits, "logits")[:, 0]

    _, logits = jax.lax.scan(head_tok, None, jnp.swapaxes(hn, 0, 1))
    return jnp.swapaxes(logits, 0, 1), cache  # (B, W, V)


def paged_draft_loop(params: dict, token: Array, pos: Array, n_valid: Array,
                     page_table: Array, cache: dict, cfg: ModelConfig,
                     k: int, *, sample=None, constrain: Constrain = _id,
                     compute_dtype=jnp.bfloat16
                     ) -> Tuple[Array, Array, dict]:
    """``k`` draft-model decode steps fused into one compiled program.

    Row ``b`` starts from ``token[b]`` (its last emitted token) at cache
    position ``pos[b]`` and autoregressively proposes ``k`` tokens,
    writing the draft model's KV as it goes (masked to the trash page
    past the row's ``n_valid`` window).  Fusing the loop is where the
    speculative win comes from at small scale: one dispatch proposes what
    would otherwise cost ``k`` engine steps.

    ``sample``: optional ``(logits (B, V), off) -> (next (B,) int32,
    probs (B, V))`` callback drawing each proposal and reporting the
    distribution it was drawn from (the speculative engine passes the
    serving stack's per-request sampler; the rejection-sampling
    correction needs exactly the ``q`` each proposal came from).  The
    default is greedy argmax with a one-hot ``q`` — the same thing the
    T=0 sampler computes, so greedy is one code path, not two.

    The scan runs ``k+1`` steps: the final step is write-only (its
    proposal is discarded), so the KV of the *last* proposal is in the
    draft cache too.  Without it, a fully-accepted window would leave the
    draft cache with a hole at that position — the next round's draft
    would attend to zeros there, and acceptance would decay even with a
    perfect draft (an identical draft model must accept at exactly 1.0;
    ``tests/test_speculative.py`` pins that).

    Returns ``(draft (B, k) int32, q (B, k, V), updated draft cache)``.
    """
    if sample is None:
        def sample(logits, off):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, jax.nn.one_hot(nxt, logits.shape[-1],
                                       dtype=logits.dtype)

    def body(carry, off):
        tok, cache = carry
        logits, cache = paged_decode_step(
            params, tok, pos + off, page_table, cache, cfg,
            constrain=constrain, compute_dtype=compute_dtype,
            write_ok=off < n_valid)
        nxt, q = sample(logits[:, 0], off)
        return (nxt[:, None], cache), (nxt, q)

    (_, cache), (toks, qs) = jax.lax.scan(
        body, (token, cache), jnp.arange(k + 1, dtype=jnp.int32))
    return toks.T[:, :k], jnp.swapaxes(qs, 0, 1)[:, :k], cache  # (B, k, ...)


def paged_prefill_chunk(params: dict, tokens: Array, start: Array,
                        n_valid: Array, page_row: Array, cache: dict,
                        cfg: ModelConfig, *, constrain: Constrain = _id,
                        compute_dtype=jnp.bfloat16) -> Tuple[Array, dict]:
    """One chunk of a single request's prefill against the paged cache.

    tokens: (1, cs) right-padded to the engine's fixed chunk width (so
    every prompt length reuses one compiled program); start / n_valid:
    traced int32 scalars (tokens already done / real tokens in this
    chunk); page_row: (max_pages,) int32.

    Returns ``(logits (1, 1, V) f32 at the chunk's last valid position,
    updated cache)`` — the logits only mean anything on the final chunk,
    where they sample the request's first token exactly as the
    full-sequence prefill would.  The pools ride the layer scan's carry
    and each layer writes the chunk's rows in place
    (:func:`_scan_paged_layers`).
    """
    if not supports_paged(cfg):
        raise ValueError(f"family {cfg.family!r} has no paged prefill path")
    cd = compute_dtype
    h = params["embed"].astype(cd)[tokens]
    h = constrain(h, "activation")

    def layer(hh, lp, kp, vp, i, win):
        out, pools = A.paged_prefill_chunk(
            lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
            start, n_valid, kp, vp, i, page_row, win)
        hh = constrain(hh + out, "activation")
        mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
        hh = constrain(hh + _mlp_out(lp, mlp_in, cfg, constrain, cd),
                       "activation")
        return hh, pools

    h, cache = _scan_paged_layers(layer, h, params, cache, cfg)
    last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
    last = L.rms_norm(last, params["final_norm"], cfg.norm_eps)
    logits = (last @ params["lm_head"].astype(cd)).astype(jnp.float32)
    return constrain(logits, "logits"), cache


def prefill(params: dict, tokens: Array, cfg: ModelConfig, max_len: int, *,
            constrain: Constrain = _id,
            extra_embeds: Optional[Array] = None,
            compute_dtype=jnp.bfloat16) -> Tuple[Array, dict]:
    """Process a prompt, returning last-position logits + populated cache.

    Only attention families keep a positional cache; SSM/hybrid prefill uses
    the forward pass then (for simplicity and dry-run purposes) primes the
    recurrent state with a short replay — full recurrent prefill is the
    chunked SSD scan itself.
    """
    cd = compute_dtype
    b, s = tokens.shape
    h = params["embed"].astype(cd)[tokens]
    h = constrain(h, "activation")
    windows = window_flags(cfg)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    if cfg.is_encdec:
        assert extra_embeds is not None
        enc = _run_encoder(cfg, params["encoder"], extra_embeds.astype(cd),
                           constrain, remat=False)
        h = h + params["pos_embed"][:s].astype(cd)
        nkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim

        def body(carry, lp):
            hh = carry
            out, (kc, vc) = A.prefill_with_cache(
                lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
                positions, None, max_len, constrain=constrain)
            hh = hh + out
            c_out = A.cross_attention(
                lp["cross"], L.rms_norm(hh, lp["ln_cross"], cfg.norm_eps),
                enc, cfg, constrain=constrain)
            hh = hh + c_out
            xk = (enc @ lp["cross"]["wk"].astype(cd)).reshape(b, -1, nkv, hd)
            xv = (enc @ lp["cross"]["wv"].astype(cd)).reshape(b, -1, nkv, hd)
            m = lp["mlp"]
            out = L.gated_mlp(L.rms_norm(hh, lp["ln2"], cfg.norm_eps),
                              m["w_gate"].astype(cd), m["w_up"].astype(cd),
                              m["w_down"].astype(cd), cfg.act)
            return hh + out, (kc, vc, xk, xv)

        h, (ck, cv, xk, xv) = jax.lax.scan(body, h, params["layers"])
        cache = {"k": ck, "v": cv, "cross_k": xk, "cross_v": xv, "enc": enc}

    elif cfg.family == "ssm":
        def body(carry, xs):
            hh = carry
            lp, = xs
            out, st = MB.mamba_forward(
                lp["mamba"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
                return_state=True, constrain=constrain)
            return constrain(hh + out, "activation"), st

        h, states = jax.lax.scan(body, h, (params["layers"],))
        cache = {"mamba": states}

    elif cfg.is_hybrid:
        period = cfg.attn_every

        def body(carry, xs):
            hh = carry
            lps = xs
            new_caches = {}
            for p_ in range(period):
                lp = lps[f"pos{p_}"]
                if "mamba" in lp:
                    out, st = MB.mamba_forward(
                        lp["mamba"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                        cfg, return_state=True, constrain=constrain)
                    hh = hh + out
                    new_caches[f"pos{p_}"] = {"mamba": st}
                else:
                    out, (kc, vc) = A.prefill_with_cache(
                        lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps),
                        cfg, positions, None, max_len)
                    hh = hh + out
                    new_caches[f"pos{p_}"] = {"k": kc, "v": vc}
                mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
                if "moe" in lp:
                    out = MOE.moe_apply(lp["moe"], mlp_in, cfg, constrain)
                elif "amm_mlp" in lp:
                    out = AMM.amm_mlp_apply(lp["amm_mlp"], mlp_in, cfg, constrain)
                else:
                    m = lp["mlp"]
                    out = L.gated_mlp(mlp_in, m["w_gate"].astype(cd),
                                      m["w_up"].astype(cd),
                                      m["w_down"].astype(cd), cfg.act)
                hh = constrain(hh + out, "activation")
            return hh, new_caches

        h, cache = jax.lax.scan(body, h, params["layers"])

    else:
        if extra_embeds is not None:
            h = jnp.concatenate([extra_embeds.astype(cd), h], axis=1)
            positions = jnp.broadcast_to(
                jnp.arange(h.shape[1]), (b, h.shape[1]))

        def body(carry, xs):
            hh = carry
            lp, win = xs
            out, (kc, vc) = A.prefill_with_cache(
                lp["attn"], L.rms_norm(hh, lp["ln1"], cfg.norm_eps), cfg,
                positions, win, max_len, constrain=constrain)
            hh = constrain(hh + out, "activation")
            mlp_in = L.rms_norm(hh, lp["ln2"], cfg.norm_eps)
            hh = constrain(hh + _mlp_out(lp, mlp_in, cfg, constrain, cd),
                           "activation")
            return hh, (kc, vc)

        h, (ck, cv) = jax.lax.scan(body, h, (params["layers"], windows))
        cache = {"k": ck, "v": cv}

    h = L.rms_norm(h[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"].astype(cd)).astype(jnp.float32)
    return constrain(logits, "logits"), cache
