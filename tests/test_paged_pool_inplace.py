"""The paged programs update the KV pool in place.

Every paged program carries the whole ``(L, P, page_size, n_kv, hd)`` pool
through its layer scan and scatters only the new tokens' rows into it
(``models/model.py::_scan_paged_layers``).  These tests pin that down at
two levels: the optimised HLO of a donated program holds no copy,
broadcast or dynamic-update-slice of the whole pool, and after a decode
step or a prefill chunk every pool entry but the written slots and the
trash page is bit-unchanged while the written slots hold the new K/V.

Pools are float32: bf16 on the CPU backend adds emulation converts that
say nothing about the TPU program.
"""
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as MD

# ops that touch every element of the pool; an in-place update has none
_WHOLE_POOL_OPS = ("copy", "copy-start", "broadcast", "dynamic-update-slice")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen3-14b", reduced=True)
    params = MD.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def _programs(cfg, params, cache, b=4, max_pages=4, chunk=32):
    """(name, donated jit, example args) of each paged program."""
    decode = jax.jit(
        lambda p, t, pos, pt, c: MD.paged_decode_step(
            p, t, pos, pt, c, cfg, compute_dtype=jnp.float32),
        donate_argnums=(4,))
    prefill = jax.jit(
        lambda p, t, s, n, r, c: MD.paged_prefill_chunk(
            p, t, s, n, r, c, cfg, compute_dtype=jnp.float32),
        donate_argnums=(5,))
    verify = jax.jit(
        lambda p, t, pos, n, pt, c: MD.paged_verify_step(
            p, t, pos, n, pt, c, cfg, compute_dtype=jnp.float32,
            backend="fused"),
        donate_argnums=(5,))
    pt = jnp.zeros((b, max_pages), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    return {
        "decode": (decode, (params, jnp.zeros((b, 1), jnp.int32), pos, pt,
                            cache)),
        "prefill": (prefill, (params, jnp.zeros((1, chunk), jnp.int32),
                              jnp.int32(0), jnp.int32(5),
                              jnp.zeros((max_pages,), jnp.int32), cache)),
        "verify_fused": (verify, (params, jnp.zeros((b, 3), jnp.int32), pos,
                                  jnp.full((b,), 3, jnp.int32), pt, cache)),
    }


@pytest.mark.parametrize("program", ["decode", "prefill", "verify_fused"])
def test_no_whole_pool_copy_in_optimised_hlo(model, program):
    cfg, params = model
    cache = MD.init_paged_cache(cfg, 65, 16, jnp.float32)
    fn, args = _programs(cfg, params, cache)[program]
    hlo = fn.lower(*args).compile().as_text()
    pool = "f32[" + ",".join(map(str, cache["k"].shape)) + "]"
    ops = Counter(re.findall(
        re.escape(pool) + r"\{[^}]*\} ([\w-]+)\(", hlo))
    assert ops["scatter"] >= 2, ops  # the K and V writes, in place
    whole = {op: n for op, n in ops.items() if op in _WHOLE_POOL_OPS}
    assert not whole, f"{program} passes over the whole pool: {whole}"


def _random_pools(cfg, num_pages, page_size, seed):
    shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
             cfg.resolved_head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return {"k": jax.random.normal(kk, shape, jnp.float32),
            "v": jax.random.normal(kv, shape, jnp.float32)}


def _assert_only_slots_changed(before, after, slots, trash):
    """Every entry but ``slots`` (a list of (phys, off), every layer) and
    the trash page is bit-unchanged."""
    keep = np.ones(before.shape[1:3], bool)
    for phys, off in slots:
        keep[phys, off] = False
    keep[trash] = False
    np.testing.assert_array_equal(after[:, keep], before[:, keep])


def test_decode_step_writes_only_its_tokens(model):
    """One decode step of 3 live rows (one with no request) changes, per
    layer, only each live row's (phys, off) slot and the trash page; the
    written slots hold the K/V that the slot-cache decode writes."""
    cfg, params = model
    ps, max_pages, b = 8, 3, 4
    trash = b * max_pages
    pools = _random_pools(cfg, trash + 1, ps, seed=1)
    # a shuffled physical layout; row 3 has no request (all trash)
    perm = np.random.default_rng(2).permutation(trash)
    pt = np.full((b, max_pages), trash, np.int32)
    pt[:3] = perm[:3 * max_pages].reshape(3, max_pages)
    pos = np.array([5, 17, 8, 0], np.int32)
    token = jnp.asarray([[7], [300], [42], [0]], jnp.int32)
    before = jax.tree.map(np.asarray, pools)
    _, after = MD.paged_decode_step(
        params, token, jnp.asarray(pos), jnp.asarray(pt), pools, cfg,
        compute_dtype=jnp.float32)
    after = jax.tree.map(np.asarray, after)
    slots = [(pt[r, pos[r] // ps], pos[r] % ps) for r in range(3)]

    # the slot cache holding the same history, at the same view length
    s_len = max_pages * ps
    slot = {kk: jnp.asarray(before[kk][:, pt].reshape(
        cfg.num_layers, b, s_len, *before[kk].shape[3:])) for kk in "kv"}
    _, slot = MD.decode_step(params, token, jnp.asarray(pos), slot, cfg,
                             compute_dtype=jnp.float32)
    for kk in "kv":
        _assert_only_slots_changed(before[kk], after[kk], slots, trash)
        for r, (phys, off) in enumerate(slots):
            np.testing.assert_array_equal(
                after[kk][:, phys, off], np.asarray(slot[kk][:, r, pos[r]]),
                err_msg=f"{kk} row {r}")


def test_prefill_chunk_writes_only_its_tokens(model):
    """A second prefill chunk (start 16, 5 real tokens of a 16-wide chunk)
    changes, per layer, only its 5 tokens' slots and the trash page; the
    written slots hold the K/V that the full-sequence prefill computes."""
    cfg, params = model
    ps, max_pages, cs = 8, 4, 16
    trash = 2 * max_pages
    pools = _random_pools(cfg, trash + 1, ps, seed=3)
    row = np.random.default_rng(4).permutation(trash)[:max_pages]
    row = row.astype(np.int32)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 21)
    prompt = prompt.astype(np.int32)
    chunks = [(0, prompt[:16]), (16, prompt[16:])]
    for start, toks in chunks:
        padded = np.zeros((1, cs), np.int32)
        padded[0, :len(toks)] = toks
        before = jax.tree.map(np.asarray, pools)
        _, pools = MD.paged_prefill_chunk(
            params, jnp.asarray(padded), jnp.int32(start),
            jnp.int32(len(toks)), jnp.asarray(row), pools, cfg,
            compute_dtype=jnp.float32)
    after = jax.tree.map(np.asarray, pools)
    slots = [(row[p // ps], p % ps) for p in range(16, 21)]

    _, ref = MD.prefill(params, jnp.asarray(prompt[None]), cfg,
                        max_pages * ps, compute_dtype=jnp.float32)
    for kk in "kv":
        _assert_only_slots_changed(before[kk], after[kk], slots, trash)
        for i, (phys, off) in enumerate(slots):
            np.testing.assert_allclose(
                after[kk][:, phys, off], np.asarray(ref[kk][:, 0, 16 + i]),
                rtol=1e-5, atol=1e-5, err_msg=f"{kk} position {16 + i}")
