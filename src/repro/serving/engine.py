"""Serving engines: continuous batching over a paged KV cache.

Two engines share one request API (``submit`` / ``cancel`` / ``step`` /
``run_until_drained``):

  * :class:`ServeEngine` — the continuous-batching runtime: a host-side
    scheduler (``serving/scheduler.py``: FCFS + priority admission,
    page-fault eviction with host swap, cancellation, per-request
    max-token budgets) over a paged KV cache (``serving/kv_cache.py``:
    fixed-size pages, free-list allocator, per-request page tables) with
    **chunked prefill** — long prompts advance one fixed-width chunk per
    step and interleave with decode instead of stalling the batch.  Every
    prompt length reuses the same two compiled programs (one chunk shape,
    one decode shape).  With ``mesh=`` the engine is sharded: params by
    the PR-3 rules, pages over the DP axis
    (``distributed/sharding.py::paged_cache_shardings``), prefill/decode
    as jitted calls with ``NamedSharding``-constrained donations.

  * :class:`FixedSlotEngine` — the PR-3 fixed-slot engine: one
    ``(L, slots, max_len, …)`` cache buffer, whole-prompt eager prefill on
    admission.  Kept as the **differential-test oracle** (the paged
    engine's int-LUT token streams must bit-match it —
    ``tests/test_serving.py``) and as the serving path for families
    without a paged layout (SSM / hybrid / enc-dec).

Both engines produce token streams bit-identical to sequential
one-request-at-a-time decoding; the paged engine additionally guarantees
this under page-pressure eviction (pages are swapped to host and restored
bit-exactly) and any admission order.

Both engines also share one per-request stochastic sampler
(``serving/sampling.py``, routed through :func:`_sample_batch`):
``submit(..., sampling=SamplingParams(...))`` turns on temperature /
top-k / top-p sampling with a per-request seed whose stream is
independent of batch composition and survives eviction + host swap.  The
default ``SamplingParams()`` is greedy (T=0), which reduces to the
historical argmax **bit-exactly** — the differential guarantees above are
the T=0 special case, pinned by ``tests/test_serving_golden.py``; the
stochastic regime is pinned distributionally by ``tests/test_sampling.py``
(see docs/sampling.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import warnings
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (MeshAxes, batch_spec,
                                        cache_shardings, make_constrainer,
                                        paged_cache_shardings,
                                        param_shardings)
from repro.models import model as MD
from repro.models.config import ModelConfig
from repro.serving import sampling as S
from repro.serving import scheduler as SCH
from repro.serving.handle import RequestHandle, _step_engine_async
from repro.serving.kv_cache import PagedKVCache
from repro.serving.obs import NULL_RECORDER, STEP_SPAN, log
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Request, Scheduler

Array = jax.Array
_NO_SPAN = contextlib.nullcontext()

# loose sampling kwargs `submit` still accepts one release behind a
# DeprecationWarning (pass a frozen SamplingParams instead)
_LEGACY_SAMPLING_KW = ("temperature", "top_k", "top_p", "seed")


def _resolve_sampling(sampling: Optional[SamplingParams],
                      legacy: Dict) -> SamplingParams:
    """Merge the deprecated loose sampling kwargs into a SamplingParams."""
    unknown = sorted(set(legacy) - set(_LEGACY_SAMPLING_KW))
    if unknown:
        raise TypeError(
            f"submit() got unexpected keyword argument(s) {unknown}")
    if legacy:
        warnings.warn(
            f"submit(**{sorted(legacy)}) loose sampling kwargs are "
            "deprecated; pass sampling=SamplingParams(...) instead",
            DeprecationWarning, stacklevel=3)
        if sampling is not None:
            raise TypeError(
                "pass either sampling=SamplingParams(...) or loose "
                "sampling kwargs, not both")
        return SamplingParams(**legacy)
    return sampling if sampling is not None else SamplingParams()


def _shape_tree(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _splice_artifact(art, params, cfg: ModelConfig, mesh):
    """Validate a loaded ``amm_lm`` artifact against ``cfg``, splice its
    LUT-MU tables into the dense params tree, and enable the AMM path with
    the artifact's recorded settings (shared by every engine — the
    speculative engine calls it once per bundle half)."""
    from repro.compiler.artifact import ArtifactError

    if art.kind != "amm_lm":
        raise ArtifactError(
            f"ServeEngine needs an amm_lm artifact, got {art.kind!r}")
    if art.manifest.get("arch") != cfg.name:
        raise ArtifactError(
            f"artifact was compiled for arch {art.manifest.get('arch')!r}"
            f", engine config is {cfg.name!r}")
    # arch name alone doesn't pin geometry (reduced configs share it)
    if art.manifest.get("num_layers") != cfg.num_layers:
        raise ArtifactError(
            f"artifact has {art.manifest.get('num_layers')} layers, "
            f"config expects {cfg.num_layers} (reduced vs full?)")
    # int4 artifacts pack two LUT columns per stored byte; the manifest
    # records the true column count
    d_out = art.manifest.get("int4_cols", {}).get(
        "layer0/lut_down", art.tensors["layer0/lut_down"].shape[-1])
    if d_out != cfg.d_model:
        raise ArtifactError(
            f"artifact d_model {d_out} != config d_model {cfg.d_model}")
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True,
                                     **art.manifest["amm"]))
    want = art.manifest.get("mesh")
    if want and mesh is not None:
        have = {ax: int(n) for ax, n in mesh.shape.items()}
        if {k: int(v) for k, v in want.items()} != have:
            log("serve", f"note: artifact was compiled for mesh {want}, "
                f"serving on {have}")
    return art.splice_lm_params(params), cfg


def _artifact_params_cfg(artifact_path, params, cfg: ModelConfig, mesh):
    """Load an ``amm_lm`` artifact from disk and splice it (see
    :func:`_splice_artifact`)."""
    from repro.compiler.artifact import load_artifact

    return _splice_artifact(load_artifact(artifact_path), params, cfg, mesh)


class _Phase:
    """One phase span of an engine step, entered once per use (the
    phases of a step follow each other and never nest)."""

    __slots__ = ("obs", "name", "after", "_kw", "_ann", "_t0")

    def __init__(self, obs, name: str, after: Optional[str] = None):
        self.obs, self.name, self.after = obs, name, after
        self._kw = {"after": after} if after else {}
        self._ann = self._t0 = None

    def __enter__(self):
        # no annotation at all while no profiler session collects: the
        # check costs a fifth of an annotation
        ann = jax.profiler.TraceAnnotation
        self._ann = ann(self.name, **self._kw) if ann.is_enabled() else None
        if self._ann is not None:
            self._ann.__enter__()
        if self.obs:
            self._t0 = self.obs.now()

    def __exit__(self, *exc):
        if self.obs:
            self.obs.on_phase(self.name, self._t0, self.obs.now(),
                              self.after)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _Step:
    """The ``serve.step`` span around one engine step; ``count`` is the
    engine's step counter."""

    __slots__ = ("obs", "count", "_ann")

    def __init__(self, obs):
        self.obs, self.count, self._ann = obs, 0, None

    def __enter__(self):
        self.count += 1
        ann = jax.profiler.StepTraceAnnotation
        self._ann = (ann(STEP_SPAN, step_num=self.count)
                     if ann.is_enabled() else None)
        if self._ann is not None:
            self._ann.__enter__()
        if self.obs:
            self.obs.on_step_begin(self.count)

    def __exit__(self, *exc):
        if self.obs:
            self.obs.on_step_end()
        if self._ann is not None:
            self._ann.__exit__(*exc)


class StepSpans:
    """The spans of an engine's steps, shared by every engine so the
    phase names cannot drift between them (``obs.STEP_PHASES``).

    ``with spans.step:`` wraps one engine step; each other attribute is
    one phase inside it.  Every span is a ``jax.profiler`` annotation on
    the profiler's clock while a profiler session is collecting, and,
    with a recorder attached, a phase of the recorder's step record
    (``Recorder.steps``).  With neither, a span costs well under a
    microsecond of host time."""

    def __init__(self, obs):
        self.step = _Step(obs)
        self.schedule = _Phase(obs, "serve.schedule")
        self.kv_move = _Phase(obs, "serve.kv_move")
        self.prefill = _Phase(obs, "serve.prefill")
        self.decode = _Phase(obs, "serve.decode")
        self.sample = _Phase(obs, "serve.sample")
        # the host blocked until the sampled tokens arrive: the only
        # device wait of a step
        self.tokens_after_prefill = _Phase(obs, "serve.tokens", "prefill")
        self.tokens_after_decode = _Phase(obs, "serve.tokens", "decode")
        self.retire = _Phase(obs, "serve.retire")


def _sample_batch(obs, logits, rows_reqs, batch: int, sample_span=_NO_SPAN):
    """Draw each row's next token through the per-request sampler.

    ``logits (batch, V)`` + ``(row, request)`` pairs → ``(batch,)`` int32,
    left on the device.  A batch whose every row is greedy (T=0, the
    default; rows not listed default to greedy and their samples are
    discarded by the caller) takes the argmax program
    ``greedy_tokens_jit``; any other batch takes ``sample_tokens_jit``,
    whose T=0 rows reduce to the same argmax bit-exactly.  The choice is
    made on the host arrays ``batch_rows`` builds, with no device sync,
    as the speculative engine chooses its round.  Shared by every engine
    so sampling semantics cannot drift between them.  The dispatch runs
    in ``sample_span``; the caller reads the tokens in its own
    ``serve.tokens`` span."""
    with sample_span:
        seed, t, temp, top_k, top_p = S.batch_rows(rows_reqs, batch)
        greedy = bool(np.all(temp <= 0.0))
        if greedy:
            toks = S.greedy_tokens_jit(logits)
        else:
            toks = S.sample_tokens_jit(logits, seed, t, temp, top_k, top_p)
        if obs:
            obs.on_sample("greedy" if greedy else "sampled")
    return toks


@dataclasses.dataclass
class _Decoded:
    """A batched decode whose sampled tokens are still on the device:
    its ``(row, request)`` pairs, the ``(max_batch,)`` tokens, and the
    host stamp of its dispatch."""
    rows: List
    tokens: Array
    t0: float


@dataclasses.dataclass
class _FirstToken:
    """A final prefill chunk whose first token is still on the device."""
    chunk: SCH.PrefillChunk
    tokens: Array
    t0: float


def _bind_quality(obs, params, cfg: ModelConfig) -> None:
    """Point the recorder's quality probe (if one is attached) at this
    engine's spliced params so sampled probe replays run the model the
    engine actually serves.  ``bind`` is first-wins, so the target half
    of a speculative bundle is the one probed."""
    quality = getattr(obs, "quality", None)
    if quality is not None:
        quality.bind(params, cfg)


def _drain(engine, max_steps: int):
    """Shared ``run_until_drained`` body: step until idle, and raise —
    rather than silently return a partial result — when the step budget is
    exhausted with requests still live.  Both engines use the same default
    budget so a workload that drains on one cannot spuriously stop on the
    other."""
    done = []
    for _ in range(max_steps):
        done.extend(engine.step())
        if not engine.has_work:
            return done
    live = len(engine.sched.live()) if hasattr(engine, "sched") else (
        len(engine.queue) + len(engine.active))
    raise RuntimeError(
        f"run_until_drained: {max_steps} steps exhausted with {live} "
        f"request(s) still live ({len(done)} finished) — raise max_steps "
        "for longer workloads, or investigate a stuck schedule")


class ServeEngine:
    """Continuous-batching serving over a paged KV cache."""

    def __init__(self, params, cfg: ModelConfig, *, max_batch: int = None,
                 slots: int = None, max_len: int = 256, page_size: int = 16,
                 prefill_chunk: int = 32, num_pages: int = None,
                 prefix_cache: bool = True, compute_dtype=jnp.float32,
                 mesh=None, recorder=None, verify_backend: str = "auto"):
        if not MD.supports_paged(cfg):
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path — serve it "
                "with FixedSlotEngine")
        self.cfg = cfg
        # speculative verify-window implementation ("scan" oracle vs the
        # fused layer-major window — see models.model.paged_verify_step).
        # Resolved once here (env override included) so the jitted round
        # programs close over a fixed choice; the plain engine never
        # verifies but stores it for SpeculativeEngine and engine cloning.
        self.verify_backend = MD.resolve_verify_backend(verify_backend)
        # observability (obs.py): the recorder threads through the
        # scheduler, cache and allocator so request lifecycle, pool and
        # swap telemetry all land in one registry.  Every hook site is
        # ``if self.obs:``-guarded — the default NullRecorder is falsy, so
        # disabled cost is one host truthiness check and no device syncs.
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self.spans = StepSpans(self.obs)
        # ``slots`` is the fixed-slot engine's name for the same knob; keep
        # it as an alias so call sites migrate freely.
        self.max_batch = int(max_batch or slots or 4)
        self.max_len = max_len
        self.page_size = ps = int(page_size)
        self.prefill_chunk = int(prefill_chunk)
        self.max_pages_per_seq = mp = -(-max_len // ps)
        if num_pages is None:
            # full provisioning: no eviction unless the caller shrinks it
            num_pages = self.max_batch * mp
        self.cd = compute_dtype
        self.mesh = mesh
        self._uid = itertools.count()

        dp = 1 if mesh is None else MeshAxes.for_mesh(mesh).dp_size(mesh)
        # §Perf-C3: the int8-quantised KV cache is a model feature
        # (cfg.amm.kv_int8) — allocate the pool accordingly, matching the
        # dtype launch/dryrun.py budgets.  The decode/prefill/verify paths
        # all key the quantise-on-write off the pool dtype.
        self.kv_dtype = (jnp.int8 if (cfg.amm.enabled and cfg.amm.kv_int8)
                         else compute_dtype)
        self.kv = PagedKVCache(cfg, num_pages=num_pages, page_size=ps,
                               dtype=self.kv_dtype, pad_to=dp,
                               recorder=recorder)
        self.sched = Scheduler(
            max_batch=self.max_batch, allocator=self.kv.allocator,
            page_size=ps, max_pages_per_seq=mp,
            prefill_chunk=self.prefill_chunk, max_len=max_len,
            prefix_cache=prefix_cache, recorder=recorder)
        self._driver = None  # set by http.AsyncServer when it owns the loop

        if mesh is None:
            self._constrain = MD._id
            self.params = params
            jit_d, jit_p, jit_t = {}, {}, {}
        else:
            self._constrain = make_constrainer(cfg, mesh)
            p_sh = param_shardings(_shape_tree(params), cfg, mesh)
            self.params = jax.device_put(params, p_sh)
            c_sh = paged_cache_shardings(_shape_tree(self.kv.buffers), cfg,
                                         mesh)
            self._cache_sh = c_sh
            self.kv.buffers = jax.device_put(self.kv.buffers, c_sh)
            rep = NamedSharding(mesh, P())
            tok_sh = NamedSharding(mesh, batch_spec(mesh, self.max_batch))
            jit_d = {"in_shardings": (p_sh, tok_sh, rep, rep, c_sh),
                     "out_shardings": (None, c_sh)}
            jit_p = {"in_shardings": (p_sh, rep, rep, rep, rep, c_sh),
                     "out_shardings": (None, c_sh)}
            jit_t = {"out_shardings": tok_sh}
        constrain = self._constrain

        def _decode(params, token, pos_vec, page_table, cache):
            return MD.paged_decode_step(
                params, token, pos_vec, page_table, cache, cfg,
                constrain=constrain, compute_dtype=compute_dtype)

        def _prefill(params, tokens, start, n_valid, page_row, cache):
            return MD.paged_prefill_chunk(
                params, tokens, start, n_valid, page_row, cache, cfg,
                constrain=constrain, compute_dtype=compute_dtype)

        def _merge_tokens(sampled, host):
            # a row whose host token is -1 continues from the decode in
            # flight: its input is that decode's sampled token.  (Named
            # apart from ``_decode``: device-trace readers find the decode
            # program by the substring ``jit__decode``.)
            return jnp.where(host < 0, sampled[:, None], host)

        self._decode = jax.jit(_decode, donate_argnums=(4,), **jit_d)
        self._prefill = jax.jit(_prefill, donate_argnums=(5,), **jit_p)
        self._merge_tokens = jax.jit(_merge_tokens, **jit_t)
        # the decode whose tokens are still on the device
        self._inflight: Optional[_Decoded] = None
        self._landed_at = 0.0  # host stamp of the last decode that landed
        if self.obs:
            self.obs.register_jit_site("serve.decode", self._decode)
            self.obs.register_jit_site("serve.decode_tokens",
                                       self._merge_tokens)
            self.obs.register_jit_site("serve.prefill", self._prefill)
            self.obs.register_jit_site("sampling.sample_tokens",
                                       S.sample_tokens_jit)
            self.obs.register_jit_site("sampling.greedy_tokens",
                                       S.greedy_tokens_jit)
            _bind_quality(self.obs, self.params, self.cfg)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_artifact(cls, artifact_path, params, cfg: ModelConfig,
                      **kwargs) -> "ServeEngine":
        """Deprecated: use :func:`repro.serving.load_engine` (it sniffs
        the artifact kind and picks the engine).  Kept one release as a
        thin shim with identical behaviour."""
        warnings.warn(
            "ServeEngine.from_artifact is deprecated; use "
            "repro.serving.load_engine(artifact_path, params, cfg, "
            "engine='paged', ...)", DeprecationWarning, stacklevel=2)
        return cls._from_artifact(artifact_path, params, cfg, **kwargs)

    @classmethod
    def _from_artifact(cls, artifact_path, params, cfg: ModelConfig,
                       **kwargs) -> "ServeEngine":
        """Serve a compiled ``amm_lm`` artifact: splice its LUT-MU tables
        into ``params`` (replacing the dense MLPs) and enable the AMM path
        with the artifact's recorded settings.

        ``params`` is the dense-model params tree the artifact was compiled
        against (e.g. a restored checkpoint); the arch name must match.
        Pass ``mesh=`` to serve sharded; when the manifest records an
        intended mesh (``python -m repro.compiler lm --mesh DxM``) a
        mismatching engine mesh is reported but not rejected — the sharding
        rules re-derive a valid placement for any mesh.
        """
        params, cfg = _artifact_params_cfg(artifact_path, params, cfg,
                                           kwargs.get("mesh"))
        return cls(params, cfg, **kwargs)

    # -- API -------------------------------------------------------------
    def submit(self, prompt: List[int],
               sampling: Optional[SamplingParams] = None, *,
               max_new_tokens: int = 16, eos_id: Optional[int] = None,
               priority: int = 0, **legacy) -> RequestHandle:
        """Queue a request; returns a :class:`RequestHandle`.

        ``sampling`` is a frozen :class:`SamplingParams` (default greedy);
        all other options are keyword-only.  Loose ``temperature=`` /
        ``top_k=`` / ``top_p=`` / ``seed=`` kwargs still work one release
        behind a ``DeprecationWarning``.
        """
        req = Request(uid=next(self._uid), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      priority=priority,
                      sampling=_resolve_sampling(sampling, legacy))
        self.sched.submit(req)
        return RequestHandle(self, req)

    def cancel(self, uid: int) -> bool:
        return self.sched.cancel(uid)

    @property
    def has_work(self) -> bool:
        # a decode in flight is work: its tokens have yet to land
        return bool(self.sched.live()) or self._inflight is not None

    async def _advance_async(self) -> None:
        await _step_engine_async(self)

    def _clone_pages(self, src: int, dst: int) -> None:
        """Device copy backing one COW clone (the speculative engine
        overrides this to clone its draft cache too — both caches share
        one page table, so a clone must cover both)."""
        self.kv.clone_page(src, dst)

    def step(self) -> List[Request]:
        """One engine iteration: execute the scheduler's plan — swap-outs,
        swap-ins, copy-on-write clones, at most one prefill chunk, one
        batched decode — then land the tokens sampled before this step's
        decode and retire finished requests, each part in its phase span
        (:class:`StepSpans`).

        One decode stays in flight: the decode queued here takes the
        previous step's sampled tokens on the device, and the host reads
        those tokens only once this decode is queued behind them, so the
        host's work overlaps the device's."""
        sp = self.spans
        finished: List[Request] = []
        with sp.step:
            with sp.schedule:
                plan = self.sched.schedule()
            if plan.swap_out or plan.swap_in or plan.cow:
                # the device orders these after the decode in flight:
                # they act on the cache buffers it returns
                with sp.kv_move:
                    self._move_kv(plan)
            landing, self._inflight = self._inflight, None
            first = (self._run_prefill_chunk(plan.prefill, finished)
                     if plan.prefill is not None else None)
            if plan.decode:
                self._inflight = self._run_decode(plan.decode, landing,
                                                  finished)
            self._land(landing, first, finished)
            if self._inflight is not None:
                # counted only now, so the landing's hooks saw each
                # request as the landing decode left it
                for _, req in self._inflight.rows:
                    req.inflight += 1
        return finished

    def run_until_drained(self, max_steps: int = 10000) -> List[Request]:
        return _drain(self, max_steps)

    # -- internals ---------------------------------------------------------
    def _move_kv(self, plan: SCH.StepPlan) -> None:
        """The plan's swap-outs, swap-ins and copy-on-write clones."""
        resharded = False
        for req, old_pages in plan.swap_out:
            # the allocator already released these pages; copy them before
            # anything writes (the first writes happen below)
            self._swap_out(req, old_pages)
        for req in plan.swap_in:
            self._swap_in(req)
            resharded = True
        for clone in plan.cow:
            if clone.req.cow is None:
                continue  # dropped: its request was evicted in this plan
            self._clone_pages(clone.src, clone.dst)
            self.sched.cow_executed(clone)
            resharded = True
        if resharded and self.mesh is not None:
            # eager swap-in updates drift leaf shardings; restore them so
            # the jitted calls' explicit in_shardings (and donation) line up
            self.kv.buffers = jax.device_put(self.kv.buffers, self._cache_sh)

    def _swap_out(self, req: Request, old_pages: List[int]) -> None:
        """Copy a victim's pages to host (the speculative engine copies
        its draft cache too)."""
        req.host_kv = self.kv.gather_host(old_pages)

    def _swap_in(self, req: Request) -> None:
        self.kv.scatter_host(req.host_kv, req.pages)
        req.host_kv = None

    def _prefill_call(self, toks, chunk: SCH.PrefillChunk, page_row):
        """Run the jitted prefill program(s) for one chunk and return the
        target logits.  The ONLY prefill behaviour subclasses may change
        (the speculative engine prefills its draft cache here too) — the
        chunk bookkeeping around it stays in :meth:`_run_prefill_chunk` and
        :meth:`_retire_first` so budget/eos fixes cannot drift between
        engines."""
        logits, self.kv.buffers = self._prefill(
            self.params, jnp.asarray(toks),
            jnp.asarray(chunk.start, jnp.int32),
            jnp.asarray(chunk.n_valid, jnp.int32),
            jnp.asarray(page_row), self.kv.buffers)
        return logits

    def _run_prefill_chunk(self, chunk: SCH.PrefillChunk,
                           finished: List[Request]
                           ) -> Optional[_FirstToken]:
        """Dispatch one prefill chunk; after a prompt's final chunk, also
        its first token's sampler, and return that token for
        :meth:`_land` to read behind this step's decode.  (``finished``
        serves an engine that lands the token at once, as the
        speculative engine does.)"""
        req, obs, sp = chunk.req, self.obs, self.spans
        with sp.prefill:
            toks = np.zeros((1, self.prefill_chunk), np.int32)
            toks[0, : chunk.n_valid] = req.prompt[chunk.start:
                                                  chunk.start + chunk.n_valid]
            page_row = self.kv.page_row(req.pages, self.max_pages_per_seq)
            t0 = obs.now() if obs else 0.0
            logits = self._prefill_call(toks, chunk, page_row)
            req.pf_done += chunk.n_valid
            if req.pf_done == len(req.prompt):
                return _FirstToken(chunk, _sample_batch(
                    obs, logits[0, -1:], [(0, req)], 1), t0)
            if obs:
                # non-final chunk: the dispatch window (no host sync
                # happens here, so the span measures host+dispatch work)
                obs.on_prefill(req, chunk.start // self.prefill_chunk,
                               chunk.n_valid, t0, obs.now())
        return None

    def _run_decode(self, decode, landing: Optional[_Decoded],
                    finished: List[Request]) -> Optional[_Decoded]:
        """Dispatch one batched decode and its sampler, and return it with
        the sampled tokens still on the device: the next step lands them
        (:meth:`_land`) once its own decode is queued behind this one.
        A row whose last token is still in flight takes it from the
        ``landing`` decode's device output (``serve.decode_tokens``): a
        request keeps its row while it runs, so the row is the same."""
        obs, sp = self.obs, self.spans
        with sp.decode:
            token = np.zeros((self.max_batch, 1), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            table = np.full((self.max_batch, self.max_pages_per_seq),
                            self.kv.trash, np.int32)
            for row, req in decode:
                token[row, 0] = -1 if req.inflight else req.generated[-1]
                pos[row] = req.next_pos
                table[row, : len(req.pages)] = req.pages
            t0 = obs.now() if obs else 0.0
            if token.min() < 0:
                token = self._merge_tokens(landing.tokens, token)
            logits, self.kv.buffers = self._decode(
                self.params, jnp.asarray(token), jnp.asarray(pos),
                jnp.asarray(table), self.kv.buffers)
            logits = logits[:, 0]
        toks = _sample_batch(obs, logits, decode, self.max_batch, sp.sample)
        return _Decoded(decode, toks, t0)

    def _land(self, landing: Optional[_Decoded],
              first: Optional[_FirstToken], finished: List[Request]) -> None:
        """Read the tokens sampled before this step's decode, in the
        device's order — the ``landing`` decode of the previous step,
        then this step's ``first`` token — and retire: append each token,
        run the hooks, and retire the requests that are done."""
        obs, sp = self.obs, self.spans
        if landing is not None:
            with sp.tokens_after_decode:
                decoded = np.asarray(landing.tokens)
            t1 = obs.now() if obs else 0.0
        if first is not None:
            with sp.tokens_after_prefill:
                first_tok = int(np.asarray(first.tokens)[0])
        if landing is not None or first is not None or obs:
            with sp.retire:
                if landing is not None:
                    self._retire_decode(landing, decoded, t1, finished)
                if first is not None:
                    self._retire_first(first, first_tok, finished)
                if obs:
                    obs.sample_pool(self.kv.allocator)
                    obs.poll_jit()

    def _retire_decode(self, landing: _Decoded, decoded: np.ndarray,
                       t1: float, finished: List[Request]) -> None:
        """Append a landed decode's tokens (read by the host at ``t1``)."""
        obs = self.obs
        if obs:
            # the device ran this decode after the previous one landed,
            # so its window starts no earlier than that
            obs.on_decode(landing.rows, max(landing.t0, self._landed_at),
                          t1)
            self._landed_at = t1
        discarded = {"eos": 0, "cancel": 0}
        for row, req in landing.rows:
            req.inflight -= 1
            if req.done:
                # retired on an EOS, or cancelled, after this decode was
                # queued: the row-step is wasted and its token dropped
                discarded["cancel" if req.cancelled else "eos"] += 1
                continue
            req.generated.append(int(decoded[row]))
            if obs:
                obs.on_tokens(req, 1, t1)
            if req.budget_reached(self.max_len):
                self.sched.retire(req)
                finished.append(req)
        if obs:
            for reason, n in discarded.items():
                if n:
                    obs.on_discard(reason, n)

    def _retire_first(self, first: _FirstToken, tok: int,
                      finished: List[Request]) -> None:
        """Append a prompt's first token, close its prefill, and retire it
        if that token ends it."""
        chunk, obs = first.chunk, self.obs
        req = chunk.req
        req.generated.append(tok)
        if obs:
            t1 = obs.now()
            obs.on_prefill(req, chunk.start // self.prefill_chunk,
                           chunk.n_valid, first.t0, t1)
            obs.on_tokens(req, 1, t1, source="prefill")
        # prefill_finished first — it indexes the prompt pages for prefix
        # reuse, which a budget-limited request still provides
        self.sched.prefill_finished(req)
        if req.budget_reached(self.max_len):
            self.sched.retire(req)
            finished.append(req)


class FixedSlotEngine:
    """The PR-3 fixed-slot engine: continuous batching over fixed decode
    slots with one ``(L, slots, max_len, …)`` cache buffer and whole-prompt
    eager prefill on admission.  The paged engine's differential-test
    oracle, and the serving path for SSM / hybrid / enc-dec families."""

    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 max_len: int = 256, compute_dtype=jnp.float32, mesh=None,
                 recorder=None):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.cd = compute_dtype
        self.mesh = mesh
        # same zero-overhead-off observability contract as ServeEngine
        # (no scheduler here, so lifecycle hooks fire from the engine)
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self.spans = StepSpans(self.obs)
        self.queue: Deque[Request] = deque()
        self.active: Dict[int, Request] = {}  # slot -> request
        self.pos = np.zeros(slots, dtype=np.int64)  # per-slot next position
        self._uid = itertools.count()
        self._driver = None  # set by http.AsyncServer when it owns the loop

        cache = MD.init_cache(cfg, slots, max_len, compute_dtype)
        if mesh is None:
            self._constrain = MD._id
            self.params = params
            self.cache = cache
            jit_kwargs = {}
        else:
            # Sharded serving: rule-engine placement for params (LUT tables
            # TP-shard over codebooks) and the slot cache (slots DP-shard),
            # then jit with explicit shardings so the donated cache buffer
            # round-trips in place.
            self._constrain = make_constrainer(cfg, mesh)
            p_sh = param_shardings(_shape_tree(params), cfg, mesh)
            self.params = jax.device_put(params, p_sh)
            c_sh = cache_shardings(_shape_tree(cache), cfg, mesh, batch=slots)
            self._cache_sh = c_sh
            self.cache = jax.device_put(cache, c_sh)
            tok_sh = NamedSharding(mesh, batch_spec(mesh, slots))
            rep = NamedSharding(mesh, P())
            jit_kwargs = {"in_shardings": (p_sh, tok_sh, rep, c_sh),
                          "out_shardings": (None, c_sh)}
        constrain = self._constrain

        def _decode(params, token, pos_vec, cache):
            # pos_vec: (slots,) — each slot decodes at its own offset, so
            # staggered admissions stay bit-identical to sequential decode.
            logits, cache = MD.decode_step(
                params, token, pos_vec, cache, cfg, constrain=constrain,
                compute_dtype=compute_dtype)
            return logits, cache

        self._decode = jax.jit(_decode, donate_argnums=(3,), **jit_kwargs)
        if self.obs:
            self.obs.register_jit_site("fixed.decode", self._decode)
            self.obs.register_jit_site("sampling.sample_tokens",
                                       S.sample_tokens_jit)
            self.obs.register_jit_site("sampling.greedy_tokens",
                                       S.greedy_tokens_jit)
            _bind_quality(self.obs, self.params, self.cfg)

    # -- construction ------------------------------------------------------
    @classmethod
    def from_artifact(cls, artifact_path, params, cfg: ModelConfig,
                      **kwargs) -> "FixedSlotEngine":
        """Deprecated: use :func:`repro.serving.load_engine` with
        ``engine='fixed'``.  Kept one release as a thin shim."""
        warnings.warn(
            "FixedSlotEngine.from_artifact is deprecated; use "
            "repro.serving.load_engine(artifact_path, params, cfg, "
            "engine='fixed', ...)", DeprecationWarning, stacklevel=2)
        return cls._from_artifact(artifact_path, params, cfg, **kwargs)

    @classmethod
    def _from_artifact(cls, artifact_path, params, cfg: ModelConfig,
                       **kwargs) -> "FixedSlotEngine":
        """Serve a compiled ``amm_lm`` artifact through fixed slots (see
        :meth:`ServeEngine._from_artifact`)."""
        params, cfg = _artifact_params_cfg(artifact_path, params, cfg,
                                           kwargs.get("mesh"))
        return cls(params, cfg, **kwargs)

    # -- API -------------------------------------------------------------
    def submit(self, prompt: List[int],
               sampling: Optional[SamplingParams] = None, *,
               max_new_tokens: int = 16, eos_id: Optional[int] = None,
               priority: int = 0, **legacy) -> RequestHandle:
        """Queue a request; returns a :class:`RequestHandle` (same
        contract as :meth:`ServeEngine.submit`)."""
        del priority  # fixed-slot admission is strictly FIFO
        req = Request(uid=next(self._uid), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      sampling=_resolve_sampling(sampling, legacy))
        self.queue.append(req)
        if self.obs:
            self.obs.on_submit(req)
        return RequestHandle(self, req)

    def cancel(self, uid: int) -> bool:
        """Drop a queued or active request.  Returns False when the uid
        is unknown or already finished."""
        for req in list(self.queue):
            if req.uid == uid:
                self.queue.remove(req)
                return self._mark_cancelled(req)
        for slot, req in list(self.active.items()):
            if req.uid == uid:
                del self.active[slot]
                return self._mark_cancelled(req)
        return False

    def _mark_cancelled(self, req: Request) -> bool:
        req.state = SCH.DONE
        req.cancelled = True
        req.done = True
        if self.obs:
            self.obs.on_cancel(req)
        return True

    async def _advance_async(self) -> None:
        await _step_engine_async(self)

    def _admit(self, finished: List[Request]) -> None:
        """Fill free slots: per-request prefill (batch=1 rows of the cache)."""
        obs, sp = self.obs, self.spans
        with sp.schedule:
            free = [s for s in range(self.slots) if s not in self.active]
        spliced = False
        while free and self.queue:
            with sp.prefill:
                slot = free.pop(0)
                req = self.queue.popleft()
                req.state = SCH.RUNNING  # for RequestHandle.status
                if obs:
                    obs.on_admit(req)
                    t0 = obs.now()
                tokens = jnp.asarray(req.prompt, jnp.int32)[None]
                logits, cache1 = MD.prefill(
                    self.params, tokens, self.cfg, self.max_len,
                    constrain=self._constrain, compute_dtype=self.cd)
                # splice the single-row cache into this slot
                self.cache = jax.tree.map(
                    lambda full, one: jax.lax.dynamic_update_index_in_dim(
                        full, one[:, 0].astype(full.dtype), slot, 1)
                    if one.ndim >= 2 and full.shape[1] == self.slots
                    else full, self.cache, cache1)
                spliced = True
                logits = logits[0, -1:]
            nxt = _sample_batch(obs, logits, [(0, req)], 1, sp.sample)
            with sp.tokens_after_prefill:
                nxt = np.asarray(nxt)
            with sp.retire:
                req.generated.append(int(nxt[0]))
                if obs:
                    t1 = obs.now()
                    obs.on_prefill(req, 0, len(req.prompt), t0, t1)
                    obs.on_tokens(req, 1, t1, source="prefill")
                if req.budget_reached(self.max_len):
                    req.done = True
                    req.state = SCH.DONE
                    finished.append(req)
                    free.insert(0, slot)
                    if obs:
                        obs.on_finish(req)
                    continue
                self.active[slot] = req
                self.pos[slot] = len(req.prompt)
        if spliced and self.mesh is not None:
            # the eager splice drifts leaf shardings off the rule-engine
            # placement; restore it so the sharded decode's explicit
            # in_shardings (and donation) line up.
            with sp.kv_move:
                self.cache = jax.device_put(self.cache, self._cache_sh)

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.active)

    def step(self) -> List[Request]:
        """One engine iteration: admit, batched decode, retire, each part
        in its phase span (:class:`StepSpans`)."""
        sp = self.spans
        finished: List[Request] = []
        with sp.step:
            self._admit(finished)
            if self.active:
                self._run_decode(finished)
            if self.obs:
                with sp.retire:
                    self.obs.poll_jit()
        return finished

    def _run_decode(self, finished: List[Request]) -> None:
        obs, sp = self.obs, self.spans
        rows = list(self.active.items())
        with sp.decode:
            token = np.zeros((self.slots, 1), dtype=np.int32)
            for slot, req in rows:
                token[slot, 0] = req.generated[-1] if req.generated else 0
            t0 = obs.now() if obs else 0.0
            logits, self.cache = self._decode(
                self.params, jnp.asarray(token),
                jnp.asarray(self.pos, jnp.int32), self.cache)
            logits = logits[:, 0]
        nxt = _sample_batch(obs, logits, rows, self.slots, sp.sample)
        with sp.tokens_after_decode:
            nxt = np.asarray(nxt)
        with sp.retire:
            if obs:
                t1 = obs.now()
                obs.on_decode(rows, t0, t1)
            for slot, req in rows:
                tok = int(nxt[slot])
                req.generated.append(tok)
                self.pos[slot] += 1
                if obs:
                    obs.on_tokens(req, 1, t1)
                if (len(req.generated) >= req.max_new_tokens
                        or (req.eos_id is not None and tok == req.eos_id)
                        or self.pos[slot] >= self.max_len - 1):
                    req.done = True
                    req.state = SCH.DONE
                    finished.append(req)
                    del self.active[slot]
                    if obs:
                        obs.on_finish(req)

    def run_until_drained(self, max_steps: int = 10000) -> List[Request]:
        return _drain(self, max_steps)


def _family_engine(params, cfg: ModelConfig, **kwargs):
    """Pick the continuous-batching engine when the family supports paged
    KV, else fall back to fixed slots (mapping ``max_batch`` to ``slots``
    and dropping the paged-only kwargs)."""
    if MD.supports_paged(cfg):
        return ServeEngine(params, cfg, **kwargs)
    max_batch = kwargs.pop("max_batch", None)
    if max_batch is not None:
        kwargs.setdefault("slots", max_batch)
    for k in ("page_size", "prefill_chunk", "num_pages", "prefix_cache",
              "verify_backend"):
        kwargs.pop(k, None)
    return FixedSlotEngine(params, cfg, **kwargs)


def make_engine(params, cfg: ModelConfig, **kwargs):
    """Deprecated: use :func:`repro.serving.load_engine` (``source=None``
    gives the same family dispatch).  Kept one release as a thin shim."""
    warnings.warn(
        "make_engine is deprecated; use repro.serving.load_engine(None, "
        "params, cfg, ...)", DeprecationWarning, stacklevel=2)
    return _family_engine(params, cfg, **kwargs)
