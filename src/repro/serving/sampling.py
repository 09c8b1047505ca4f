"""Stochastic sampling for the serving stack: pure jittable logit
transforms, per-request RNG key folding, and the speculative
rejection-sampling correction.

Design contract (pinned by ``tests/test_sampling.py`` and the
distributional harness in ``tests/dist_check.py``):

  * **determinism** — every random decision for a request is a pure
    function of ``(seed, emission index, role)``.  The key for the
    ``t``-th emitted token is ``fold_in(fold_in(PRNGKey(seed), t),
    role)`` — never a shared batch key, never engine state — so a
    request's stream depends only on its own :class:`SamplingParams`,
    not on batch composition, admission order, or page-fault
    eviction/host-swap (the counter is just ``req.emitted``, the tokens
    sampled so far, which swaps trivially);
  * **greedy is the T=0 special case** — a ``temperature == 0`` row of
    :func:`sample_tokens` gets a one-hot distribution at
    ``argmax(logits)``, and the exact inverse-CDF sampler maps *any*
    uniform to that argmax, so T=0 streams are bit-identical to the
    historical argmax engines (``tests/test_serving_golden.py``) even
    beside sampled rows.  A batch whose every row is greedy gets the
    same tokens from :func:`greedy_tokens`, one argmax with none of the
    transforms, which is what the engines dispatch for it;
  * **speculative correctness** — :func:`speculative_accept` implements
    the standard rejection-sampling correction (accept draft token ``x``
    with probability ``min(1, p(x)/q(x))``, resample from the normalised
    residual ``max(p - q, 0)`` on reject, sample the bonus token from
    ``p`` on full acceptance), which makes sampled speculative decoding
    distributionally identical to plain sampled decoding — and
    degenerates *bitwise* to greedy prefix matching at T=0 (one-hot
    ``p``/``q`` turn the accept test into ``draft == argmax(target)``).

Transform order is temperature → top-k → top-p (each a no-op at its
neutral setting), then softmax.  All functions are shape-polymorphic
over leading batch dims: ``logits (..., V)`` with parameters
broadcastable to ``(...)``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Decision roles: independent sub-streams per emitted-token index.  The
# plain sampler and the speculative bonus token share ROLE_SAMPLE; the
# draft's proposals, the accept test and the residual resample each get
# their own stream so the rejection-sampling theorem's independence
# assumptions hold by construction.
ROLE_SAMPLE = 0
ROLE_ACCEPT = 1
ROLE_RESIDUAL = 2
ROLE_DRAFT = 3


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (host-side, no jax arrays —
    the scheduler stays pure-host and fuzzable).

    ``temperature == 0`` is greedy argmax (bit-exact with the pre-sampling
    engines; ``top_k``/``top_p``/``seed`` are then irrelevant).
    ``top_k == 0`` disables top-k; ``top_p == 1`` disables nucleus
    filtering.  ``seed`` fully determines the request's stream given its
    prompt (see module docstring).
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got {self.top_k}")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if not 0 <= self.seed < 2**32:
            raise ValueError(f"seed must fit in uint32, got {self.seed}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0


# ---------------------------------------------------------------------------
# RNG key lifecycle.
# ---------------------------------------------------------------------------


def stream_key(seed, t, role: int):
    """Key for one random decision: ``(seed, emission index, role)``.

    Scalar in, scalar key out; jit/vmap-safe (threefry seeding is
    traceable).  Per-request folding — never a shared batch key.
    """
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    return jax.random.fold_in(jax.random.fold_in(key, jnp.asarray(t, jnp.int32)),
                              role)


def stream_uniform(seed, t, role: int) -> Array:
    """Elementwise U[0,1) draws: one per broadcast ``(seed, t)`` pair."""
    seed = jnp.asarray(seed, jnp.uint32)
    t = jnp.asarray(t, jnp.int32)
    seed, t = jnp.broadcast_arrays(seed, t)
    flat = jax.vmap(lambda s, tt: jax.random.uniform(stream_key(s, tt, role),
                                                     ()))(seed.ravel(), t.ravel())
    return flat.reshape(t.shape)


# ---------------------------------------------------------------------------
# Pure logit transforms.
# ---------------------------------------------------------------------------


def apply_temperature(logits: Array, temperature) -> Array:
    """``logits / T`` with T broadcast over the vocab axis; T <= 0 rows
    pass through unscaled (the greedy branch replaces them downstream)."""
    t = jnp.asarray(temperature, logits.dtype)
    safe = jnp.where(t > 0, t, jnp.ones_like(t))
    return logits / safe[..., None]


def apply_top_k(logits: Array, k) -> Array:
    """Keep exactly ``min(k, V)`` entries (the largest; ties broken
    toward lower vocab ids, matching ``argmax``), mask the rest to -inf.
    ``k <= 0`` disables the filter."""
    v = logits.shape[-1]
    order = jnp.argsort(logits, axis=-1, descending=True)  # stable
    ranks = jnp.argsort(order, axis=-1)
    kk = jnp.asarray(k, jnp.int32)
    limit = jnp.where((kk > 0) & (kk < v), kk, v)
    keep = ranks < limit[..., None]
    return jnp.where(keep, logits, -jnp.inf)


def apply_top_p(logits: Array, p) -> Array:
    """Nucleus filter: keep the minimal probability-sorted prefix whose
    mass reaches ``p`` (the crossing token included), mask the rest to
    -inf.  ``p >= 1`` disables the filter; the top token is always kept."""
    probs = jax.nn.softmax(logits, axis=-1)
    order = jnp.argsort(logits, axis=-1, descending=True)
    sp = jnp.take_along_axis(probs, order, axis=-1)
    csum = jnp.cumsum(sp, axis=-1)
    pp = jnp.asarray(p, logits.dtype)[..., None]
    keep_sorted = (csum - sp) < pp  # mass strictly before me < p
    keep_sorted = keep_sorted.at[..., 0].set(True)
    ranks = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    return jnp.where(pp < 1.0, jnp.where(keep, logits, -jnp.inf), logits)


def sampling_probs(logits: Array, temperature, top_k, top_p) -> Array:
    """The full transform pipeline → a probability vector per row.

    T > 0: softmax(top_p(top_k(logits / T))).  T == 0: a one-hot at
    ``argmax(logits)`` — the exact greedy distribution, which the
    inverse-CDF sampler maps to ``argmax`` for every uniform (this is
    what makes T=0 bit-exact end to end).
    """
    x = apply_temperature(logits, temperature)
    x = apply_top_k(x, top_k)
    x = apply_top_p(x, top_p)
    probs = jax.nn.softmax(x, axis=-1)
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                            dtype=probs.dtype)
    greedy = jnp.asarray(temperature) <= 0
    return jnp.where(greedy[..., None], onehot, probs)


def categorical_from_uniform(probs: Array, u: Array) -> Array:
    """Exact inverse-CDF sample: smallest index whose cumulative mass
    exceeds ``u * total`` (scaling by the total absorbs normalisation
    error, so unnormalised weights — e.g. speculative residuals — work
    directly).  Zero-probability categories are never returned; a
    one-hot distribution returns its hot index for *every* ``u``
    (including 0), which is the T=0 bit-exactness guarantee.
    """
    csum = jnp.cumsum(probs, axis=-1)
    total = csum[..., -1:]
    tok = jnp.sum((csum <= u[..., None] * total).astype(jnp.int32), axis=-1)
    return jnp.minimum(tok, probs.shape[-1] - 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Plain sampling step (both serving engines).
# ---------------------------------------------------------------------------


def sample_tokens(logits: Array, seed: Array, t: Array, temperature: Array,
                  top_k: Array, top_p: Array) -> Array:
    """One batched sampling decision: ``logits (B, V)`` + per-row
    ``(seed, t, temperature, top_k, top_p)`` → ``(B,)`` int32 tokens.

    Row ``b``'s token is a pure function of its own parameters — rows
    are fully independent (never a shared batch key).
    """
    probs = sampling_probs(logits, temperature, top_k, top_p)
    u = stream_uniform(seed, t, ROLE_SAMPLE)
    return categorical_from_uniform(probs, u)


sample_tokens_jit = jax.jit(sample_tokens)


def greedy_tokens(logits: Array) -> Array:
    """The tokens of an all-greedy batch: ``logits (B, V)`` → ``(B,)``
    int32 ``argmax``, ties to the lower vocab id.  Bit-identical to
    :func:`sample_tokens` when every row has T=0, whatever the rows'
    seed, top-k and top-p: that is the same argmax, taken without the
    sorts and the softmax whose result a T=0 row discards."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


greedy_tokens_jit = jax.jit(greedy_tokens)


def batch_rows(rows_reqs: List[Tuple[int, object]], batch: int):
    """Assemble the per-row sampling arrays for a decode/verify batch
    from ``(row, request)`` pairs.  Inactive rows default to greedy
    (T=0), whose samples the engines discard.  ``t`` is the emission
    index of the *next* token — ``req.emitted``, which counts a token
    still in flight — which is what makes streams batch-independent and
    swap/eviction-proof."""
    seed = np.zeros((batch,), np.uint32)
    t = np.zeros((batch,), np.int32)
    temp = np.zeros((batch,), np.float32)
    top_k = np.zeros((batch,), np.int32)
    top_p = np.ones((batch,), np.float32)
    for row, req in rows_reqs:
        sp = req.sampling
        seed[row] = sp.seed
        t[row] = req.emitted
        temp[row] = sp.temperature
        top_k[row] = sp.top_k
        top_p[row] = sp.top_p
    return seed, t, temp, top_k, top_p


# ---------------------------------------------------------------------------
# Speculative rejection-sampling correction.
# ---------------------------------------------------------------------------


def speculative_accept(p_probs: Array, q_probs: Array, draft: Array,
                       seed: Array, t0: Array, n_valid: Array
                       ) -> Tuple[Array, Array]:
    """The rejection-sampling correction for one draft+verify round.

    Inputs (W = window width = spec_k + 1, K = W - 1 proposals):

      * ``p_probs (B, W, V)`` — the *target's* post-transform sampling
        distribution at each window position (position ``j`` is the
        distribution of emitted-token index ``t0 + j``);
      * ``q_probs (B, K, V)`` — the *draft's* post-transform distribution
        each proposal was drawn from;
      * ``draft (B, K)`` — the proposals ``x_j ~ q_j``;
      * ``seed/t0/n_valid (B,)`` — per-request RNG seed, emission index
        of the window's first token, and the row's live window width.

    Per row: proposal ``j`` is accepted iff ``u_j * q_j(x_j) < p_j(x_j)``
    with ``u_j`` drawn from the ``(seed, t0+j, ROLE_ACCEPT)`` stream —
    i.e. with probability ``min(1, p/q)``.  The token at the first
    rejected position is resampled from the normalised residual
    ``max(p_j - q_j, 0)`` (``ROLE_RESIDUAL``); on full acceptance the
    bonus token is sampled from ``p`` at the window's last position
    (``ROLE_SAMPLE`` — the same stream a plain engine would have used
    for that emission index).  Marginally *and* jointly, the emitted
    tokens are distributed exactly as plain sampling from the target
    (``tests/dist_check.py`` proves it empirically; T=0 reduces bitwise
    to greedy prefix matching + correction token).

    Returns ``(accepted (B,) int32, emit (B, W) int32)`` — row ``b``
    emits ``emit[b, :accepted[b] + 1]``.
    """
    b, w, v = p_probs.shape
    k = w - 1
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    tj = t0[:, None] + j
    seed_b = jnp.broadcast_to(seed[:, None], (b, k))
    p_head = p_probs[:, :k]
    p_x = jnp.take_along_axis(p_head, draft[..., None], axis=-1)[..., 0]
    q_x = jnp.take_along_axis(q_probs, draft[..., None], axis=-1)[..., 0]
    u_acc = stream_uniform(seed_b, tj, ROLE_ACCEPT)
    # u*q < p  ⇔  u < p/q without the division (q(x) > 0 for sampled x);
    # strict < keeps T=0 exact: one-hot p/q give ratios exactly 0 or 1
    ok = (u_acc * q_x < p_x) & (j < (n_valid[:, None] - 1))
    accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=-1), axis=-1)
    resid = jnp.maximum(p_head - q_probs, 0.0)
    u_res = stream_uniform(seed_b, tj, ROLE_RESIDUAL)
    res_tok = categorical_from_uniform(resid, u_res)  # (B, K)
    last_pos = jnp.maximum(n_valid - 1, 0)
    p_last = jnp.take_along_axis(p_probs, last_pos[:, None, None],
                                 axis=1)[:, 0]  # (B, V)
    u_bonus = stream_uniform(seed, t0 + last_pos, ROLE_SAMPLE)
    bonus = categorical_from_uniform(p_last, u_bonus)  # (B,)
    full = accepted >= last_pos
    res_at_a = jnp.take_along_axis(
        res_tok, jnp.minimum(accepted, k - 1)[:, None], axis=-1)[:, 0]
    last = jnp.where(full, bonus, res_at_a)
    jw = jnp.arange(w, dtype=jnp.int32)[None, :]
    draft_pad = jnp.pad(draft, ((0, 0), (0, 1)))
    emit = jnp.where(jw == accepted[:, None], last[:, None], draft_pad)
    return accepted.astype(jnp.int32), emit.astype(jnp.int32)
