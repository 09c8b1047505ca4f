"""Speculative decoding: a low-resolution LUT-MU draft proposes, the
full-resolution target verifies — bit-exact greedy streams,
distribution-exact sampled streams, fewer sequential steps.

The paper's resolution configs (float32 → int4) trade accuracy for a
1.3–2.6× resource saving.  Speculative decoding converts that trade into
**pure throughput**: the cheap low-resolution draft model only *proposes*
tokens, and every proposal is checked by the full-resolution target, so
the emitted stream is — by construction, not statistically — identical to
what the target alone would produce under greedy decoding.

Round structure (one :meth:`SpeculativeEngine.step`):

  1. **draft** — one fused compiled program
     (``models/model.py::paged_draft_loop``) runs ``k`` decode steps of
     the draft model over the whole decode batch, each proposal drawn
     from the draft's *post-transform* sampling distribution ``q``
     (greedy = the T=0 one-hot special case), writing the draft's own
     paged KV cache;
  2. **verify** — one multi-token target step
     (``models/model.py::paged_verify_step``) feeds each row's last
     emitted token plus its ``k`` proposals at positions
     ``next_pos .. next_pos+k`` and returns per-position logits, from
     which the target's sampling distribution ``p`` at every window
     position is computed (``serving/sampling.py::sampling_probs``);
  3. **accept** — the standard rejection-sampling correction, in the same
     compiled program (``serving/sampling.py::speculative_accept``):
     proposal ``x_j`` is accepted with probability ``min(1,
     p_j(x_j)/q_j(x_j))``; the first rejected position is resampled from
     the normalised residual ``max(p_j - q_j, 0)``; on full acceptance a
     bonus token is drawn from ``p`` at the window's last position using
     the exact RNG stream a plain engine would have used for that
     emission index.  The emitted tokens are distributed exactly as
     plain sampling from the target — and at T=0 (one-hot ``p``/``q``)
     the accept test degenerates *bitwise* to greedy prefix matching,
     so greedy streams stay bit-identical to the plain engine.  1 to
     ``k+1`` tokens are emitted per request per round;
  4. **rollback** — positions past the accepted prefix hold rejected-draft
     K/V in both caches.  They are *garbage by construction*: the next
     window starts exactly at the first rejected position and every paged
     write precedes every read of the same position, so garbage is always
     overwritten before it can be attended to.  Pages backing only
     garbage are returned to the pool (``scheduler.Scheduler.rollback``).

Cache architecture: the draft shares the target's dense backbone (same
attention weights — a bundle differs only in LUT tables), so both KV
caches have identical geometry.  The engine therefore runs **one**
scheduler / page allocator / page table and mirrors the physical pools
(``PagedKVCache(allocator=...)``): page id ``p`` addresses the same
logical slot in both caches, and admission / chunked prefill / eviction /
host swap / cancellation all come from the PR-4 machinery unchanged —
swap simply copies both pools.

Why bit-exactness holds: the verify step issues every reduction at the
*exact* single-token :func:`~repro.models.model.paged_decode_step`
shapes — either literally (the ``scan`` oracle backend) or layer-major
with the page view gathered once per layer (the default ``fused``
backend, ``kernels/fused_verify.py``; see docs/kernels.md) — so each
accepted token's logits are bitwise the ones plain
:class:`~repro.serving.engine.ServeEngine` would have computed.  On top
of that the RNG streams line up by construction:
every draw is keyed by ``(request seed, emission index, role)``, so the
bonus token on full acceptance uses exactly the uniform the plain engine
would have used for that position.  The differential suite
(``tests/test_speculative.py``) pins greedy streams against the plain
engine across draft quality, ``k``, eviction and cancellation;
``tests/test_sampling.py`` + ``tests/dist_check.py`` pin the sampled
regime distributionally (see docs/sampling.md for the proof sketch).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving import sampling as S
from repro.serving.engine import ServeEngine, _splice_artifact
from repro.serving.kv_cache import HostKV, PagedKVCache
from repro.serving.obs import Recorder
from repro.serving.scheduler import Request

# cfg fields that must agree between target and draft: both models route
# through one page table and one verify window, so KV geometry and the
# token space are load-bearing (LUT/AMM settings are free to differ —
# that difference IS the draft).
_GEOMETRY_FIELDS = ("family", "num_layers", "d_model", "num_heads",
                    "num_kv_heads", "head_dim", "vocab_size",
                    "sliding_window", "local_global_ratio", "qk_norm",
                    "qkv_bias", "rope_theta", "norm_eps")


class SpeculativeEngine(ServeEngine):
    """Continuous-batching serving with draft-propose / target-verify."""

    def __init__(self, params, cfg: ModelConfig, draft_params, *,
                 draft_cfg: Optional[ModelConfig] = None, spec_k: int = 4,
                 **kwargs):
        if kwargs.get("mesh") is not None:
            raise NotImplementedError(
                "mesh-parallel speculative serving is an open item (see "
                "ROADMAP.md) — serve unsharded or use ServeEngine")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        # acceptance telemetry has always been on for this engine (the
        # PR-5 ad-hoc `stats` dict) — it now lives on the obs registry, so
        # default to a metrics-only recorder instead of the NullRecorder
        # to keep `stats` / `acceptance_rate` working out of the box
        if kwargs.get("recorder") is None:
            kwargs["recorder"] = Recorder(trace=False)
        super().__init__(params, cfg, **kwargs)
        self.spec_k = int(spec_k)
        self.draft_cfg = draft_cfg if draft_cfg is not None else self.cfg
        for f in _GEOMETRY_FIELDS:
            if getattr(self.cfg, f) != getattr(self.draft_cfg, f):
                raise ValueError(
                    f"draft/target geometry mismatch on {f!r}: "
                    f"{getattr(self.draft_cfg, f)!r} vs "
                    f"{getattr(self.cfg, f)!r}")
        self.draft_params = draft_params
        # verify windows write up to k+1 positions per request per step;
        # the scheduler must grow pages to cover the window up front
        self.sched.lookahead = self.spec_k + 1
        # mirror of the target pool: same page ids, the draft model's KV
        # (the shared allocator keeps its own recorder, so pool counters
        # are not double-counted; draft swap traffic IS counted — swap
        # copies both pools)
        self.kv_draft = PagedKVCache(
            self.cfg, num_pages=self.kv.num_pages, page_size=self.page_size,
            dtype=self.kv_dtype, allocator=self.kv.allocator,
            recorder=self.obs)
        assert self.kv_draft.trash == self.kv.trash
        self._draft_host: Dict[int, HostKV] = {}  # uid → swapped draft KV

        cfg_t, cfg_d, cd, k = self.cfg, self.draft_cfg, self.cd, self.spec_k
        vb = self.verify_backend  # resolved ("scan"|"fused") by ServeEngine

        def _round(pt, pd, token, pos, n_valid, table, seed, t0, temp,
                   top_k, top_p, cache_t, cache_d):
            # draft-propose, target-verify and the rejection-sampling
            # acceptance chained in ONE compiled program: the whole round
            # costs a single dispatch, which is where the tok/s win over
            # one-dispatch-per-token plain decode comes from in the
            # dispatch-bound regime
            def draft_sample(logits, off):
                # proposal for emission index t0+off from the draft's own
                # post-transform distribution, on the ROLE_DRAFT stream
                # (independent of every target-side draw)
                q = S.sampling_probs(logits, temp, top_k, top_p)
                u = S.stream_uniform(seed, t0 + off, S.ROLE_DRAFT)
                return S.categorical_from_uniform(q, u), q

            draft, q_probs, cache_d = MD.paged_draft_loop(
                pd, token, pos, n_valid, table, cache_d, cfg_d, k,
                sample=draft_sample, compute_dtype=cd)
            window = jnp.concatenate([token, draft], axis=1)  # (B, k+1)
            logits, cache_t = MD.paged_verify_step(
                pt, window, pos, n_valid, table, cache_t, cfg_t,
                compute_dtype=cd, backend=vb)
            p_probs = S.sampling_probs(logits, temp[:, None],
                                       top_k[:, None], top_p[:, None])
            accepted, emit = S.speculative_accept(
                p_probs, q_probs, draft, seed, t0, n_valid)
            return accepted, emit, cache_t, cache_d

        def _round_greedy(pt, pd, token, pos, n_valid, table,
                          cache_t, cache_d):
            # T=0 fast path, host-selected when EVERY active row is
            # greedy: skips the sampling transforms, threefry streams and
            # rejection logic entirely.  Bit-equivalent to `_round` with
            # one-hot p/q (accept degenerates to prefix matching, the
            # residual/bonus to the target argmax) — the golden tri-engine
            # test and the mixed-batch test in tests/test_speculative.py
            # pin both programs to the same greedy streams.
            draft, _, cache_d = MD.paged_draft_loop(
                pd, token, pos, n_valid, table, cache_d, cfg_d, k,
                compute_dtype=cd)
            window = jnp.concatenate([token, draft], axis=1)  # (B, k+1)
            logits, cache_t = MD.paged_verify_step(
                pt, window, pos, n_valid, table, cache_t, cfg_t,
                compute_dtype=cd, backend=vb)
            target = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            ok = (draft == target[:, :-1]) & (
                jnp.arange(k)[None, :] < n_valid[:, None] - 1)
            accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                               axis=1)
            return accepted, target, cache_t, cache_d

        def _prefill_pair(pt, pd, tokens, start, n_valid, page_row, ct, cdr):
            logits, ct = MD.paged_prefill_chunk(
                pt, tokens, start, n_valid, page_row, ct, cfg_t,
                compute_dtype=cd)
            _, cdr = MD.paged_prefill_chunk(
                pd, tokens, start, n_valid, page_row, cdr, cfg_d,
                compute_dtype=cd)
            return logits, ct, cdr

        self._round = jax.jit(_round, donate_argnums=(11, 12))
        self._round_greedy = jax.jit(_round_greedy, donate_argnums=(6, 7))
        self._prefill_pair = jax.jit(_prefill_pair, donate_argnums=(6, 7))
        if self.obs:
            self.obs.register_jit_site("spec.round", self._round)
            self.obs.register_jit_site("spec.round_greedy",
                                       self._round_greedy)
            self.obs.register_jit_site("spec.prefill_pair",
                                       self._prefill_pair)

    # -- construction ------------------------------------------------------
    @classmethod
    def _from_artifacts(cls, target_art, draft_art, params,
                        cfg: ModelConfig, **kwargs) -> "SpeculativeEngine":
        """Build from two loaded/in-memory ``amm_lm`` artifacts: both are
        spliced into the same dense params tree (they share the backbone;
        only the LUT tables differ)."""
        mesh = kwargs.get("mesh")
        params_t, cfg_t = _splice_artifact(target_art, params, cfg, mesh)
        params_d, cfg_d = _splice_artifact(draft_art, params, cfg, mesh)
        return cls(params_t, cfg_t, params_d, draft_cfg=cfg_d, **kwargs)

    @classmethod
    def _from_bundle(cls, bundle_path, params, cfg: ModelConfig,
                     **kwargs) -> "SpeculativeEngine":
        """Serve a compiled target+draft bundle
        (``python -m repro.compiler bundle``).  ``spec_k`` defaults to the
        bundle manifest's recorded suggestion."""
        from repro.compiler.artifact import load_bundle

        target, draft, manifest = load_bundle(bundle_path)
        kwargs.setdefault("spec_k", int(manifest.get("spec_k", 4)))
        return cls._from_artifacts(target, draft, params, cfg, **kwargs)

    # -- telemetry ---------------------------------------------------------
    @property
    def stats(self) -> Dict[str, int]:
        """The PR-5 telemetry dict, now a **view over the obs registry**
        (one source of truth with the Prometheus exposition and the
        benchmark cells).  Keys are back-compatible — ``rounds`` counts
        per-request round participations, ``proposed``/``accepted`` count
        draft proposals, ``emitted`` counts every token a round appended
        — plus the PR-7 split of the final window token into
        ``corrections`` (residual resample on rejection) and ``bonuses``
        (extra draw on full acceptance).  Conservation invariant, pinned
        by tests/test_speculative.py::

            emitted == accepted + corrections + bonuses
        """
        v = self.obs.registry.value
        return {"rounds": int(v("spec_request_rounds_total")),
                "proposed": int(v("spec_proposed_total")),
                "accepted": int(v("spec_accepted_total")),
                "emitted": int(v("spec_emitted_total")),
                "corrections": int(v("spec_corrections_total")),
                "bonuses": int(v("spec_bonuses_total"))}

    @property
    def acceptance_rate(self) -> float:
        """Engine-wide fraction of verified proposals accepted so far."""
        return (self.obs.registry.value("spec_accepted_total")
                / max(1, self.obs.registry.value("spec_proposed_total")))

    @property
    def mean_emitted_per_round(self) -> float:
        """Tokens emitted per request per draft+verify round (1 .. k+1)."""
        return (self.obs.registry.value("spec_emitted_total")
                / max(1, self.obs.registry.value("spec_request_rounds_total")))

    # -- API ---------------------------------------------------------------
    def cancel(self, uid: int) -> bool:
        ok = super().cancel(uid)
        if ok:
            self._draft_host.pop(uid, None)
        return ok

    # -- internals ---------------------------------------------------------
    # ``ServeEngine.step`` drives a round: swaps and copy-on-write clones
    # (both caches), at most one prefill chunk (both models), and one
    # speculative draft+verify round in place of the decode.  The round
    # reads its accept counts on the host before it returns, so unlike
    # the plain decode it leaves nothing in flight, and a prompt's first
    # token lands at once, before the round (``_run_prefill_chunk``).
    def _swap_out(self, req: Request, old_pages: List[int]) -> None:
        super()._swap_out(req, old_pages)
        self._draft_host[req.uid] = self.kv_draft.gather_host(old_pages)

    def _swap_in(self, req: Request) -> None:
        super()._swap_in(req)
        host_d = self._draft_host.pop(req.uid, None)
        if host_d is not None:
            self.kv_draft.scatter_host(host_d, req.pages)

    def _clone_pages(self, src: int, dst: int) -> None:
        """COW must cover BOTH caches: target and draft share one page
        table, so a cloned page id must carry both models' prefix KV
        (the donor's prefill wrote both — see ``_prefill_call``)."""
        self.kv.clone_page(src, dst)
        self.kv_draft.clone_page(src, dst)

    def _prefill_call(self, toks, chunk, page_row):
        """Chunked prefill through BOTH models (the draft needs its own KV
        for the prompt); the chunk bookkeeping is inherited.  The request's
        first token comes from the target logits — the same computation,
        on the same arguments, as the plain engine's prefill, so it is
        bit-identical."""
        logits, self.kv.buffers, self.kv_draft.buffers = self._prefill_pair(
            self.params, self.draft_params, jnp.asarray(toks),
            jnp.asarray(chunk.start, jnp.int32),
            jnp.asarray(chunk.n_valid, jnp.int32),
            jnp.asarray(page_row), self.kv.buffers, self.kv_draft.buffers)
        return logits

    def _run_prefill_chunk(self, chunk, finished: List[Request]) -> None:
        """The plain engine's prefill chunk, whose first token is read and
        retired at once: no decode of this engine stays in flight for the
        read to wait behind."""
        first = super()._run_prefill_chunk(chunk, finished)
        if first is not None:
            sp = self.spans
            with sp.tokens_after_prefill:
                tok = int(np.asarray(first.tokens)[0])
            with sp.retire:
                self._retire_first(first, tok, finished)

    def _run_decode(self, decode, landing, finished: List[Request]) -> None:
        """Draft k proposals, verify k+1 positions, rejection-sample the
        accepted prefix + correction/bonus token — all in one dispatch.
        The round leaves nothing in flight, so ``landing`` is ``None``."""
        obs, sp = self.obs, self.spans
        with sp.decode:
            k = self.spec_k
            token = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            n_valid = np.zeros((self.max_batch,), np.int32)
            table = np.full((self.max_batch, self.max_pages_per_seq),
                            self.kv.trash, np.int32)
            for row, req in decode:
                token[row, 0] = req.generated[-1]
                pos[row] = req.next_pos
                # window size: never verify past the request's token
                # budget or the engine's max_len (position
                # next_pos+n_valid-1 must stay a legal cache index AND
                # every emitted token must be one the plain engine could
                # also have emitted)
                n_valid[row] = min(
                    k + 1,
                    req.max_new_tokens - len(req.generated),
                    self.max_len - len(req.prompt) - len(req.generated))
                table[row, : len(req.pages)] = req.pages
            seed, t0, temp, top_k, top_p = S.batch_rows(decode,
                                                        self.max_batch)
            tw0 = obs.now() if obs else 0.0
            greedy = bool(np.all(temp <= 0.0))
            if greedy:
                # all-greedy batch (inactive rows default to T=0): the
                # fast path skips the sampling machinery — same
                # accepted/emit contract, bit-identical tokens
                (accepted, emit, self.kv.buffers,
                 self.kv_draft.buffers) = self._round_greedy(
                    self.params, self.draft_params, jnp.asarray(token),
                    jnp.asarray(pos), jnp.asarray(n_valid),
                    jnp.asarray(table), self.kv.buffers,
                    self.kv_draft.buffers)
            else:
                (accepted, emit, self.kv.buffers,
                 self.kv_draft.buffers) = self._round(
                    self.params, self.draft_params, jnp.asarray(token),
                    jnp.asarray(pos), jnp.asarray(n_valid),
                    jnp.asarray(table), jnp.asarray(seed),
                    jnp.asarray(t0), jnp.asarray(temp), jnp.asarray(top_k),
                    jnp.asarray(top_p), self.kv.buffers,
                    self.kv_draft.buffers)
        with sp.tokens_after_decode:
            accepted = np.asarray(accepted)  # (B,) accepted-prefix lengths
            emit = np.asarray(emit)          # (B, k+1) tokens to emit a row
        with sp.retire:
            if obs:
                # np.asarray above already pulled the round to host: tw1
                # covers the real wall window without adding a sync
                tw1 = obs.now()
                obs.on_decode(decode, tw0, tw1, name="spec-round")
                obs.on_spec_round("greedy" if greedy else "sampled")

            for row, req in decode:
                w = int(n_valid[row])
                a = int(accepted[row])
                req.spec_rounds += 1
                req.spec_proposed += w - 1
                # emit accepted proposals + the correction/bonus token,
                # re-checking the budget after every token exactly like
                # the plain engine's one-token steps (eos truncates the
                # window)
                emitted_n = 0
                for tok in emit[row, : a + 1]:
                    req.generated.append(int(tok))
                    emitted_n += 1
                    if req.budget_reached(self.max_len):
                        break
                # truncation-aware accounting: an eos inside the window
                # stops emission early, and only tokens that actually
                # landed count — so `emitted == accepted + corrections +
                # bonuses` holds by construction (the window's final token
                # is the correction on rejection, the bonus draw on full
                # acceptance)
                acc_emitted = min(emitted_n, a)
                final_emitted = emitted_n == a + 1
                correction = 1 if final_emitted and a < w - 1 else 0
                bonus = 1 if final_emitted and a == w - 1 else 0
                req.spec_accepted += acc_emitted
                if obs:
                    obs.on_spec_row(w - 1, acc_emitted, correction, bonus,
                                    emitted_n)
                    obs.on_tokens(req, emitted_n, tw1)
                if req.budget_reached(self.max_len):
                    self.sched.retire(req)
                    finished.append(req)
                else:
                    # positions past the new next_pos hold rejected-draft
                    # KV in both caches — free the pages backing only
                    # garbage
                    self.sched.rollback(req)
