"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Loads (or random-initialises) serving params and drives the continuous-
batching engine over a synthetic request stream — with ``--amm`` the MLPs
run through the paper's LUT-MU path.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --requests 6 --max-new 12

  # sharded serving on a faked 2x2 host mesh (data x model)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --requests 3 --mesh 2x2

  # speculative decoding from a compiled target+draft bundle
  PYTHONPATH=src python -m repro.compiler bundle --arch qwen3-14b \
      --reduced --out /tmp/lm_bundle
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --artifact /tmp/lm_bundle --speculative --spec-k 3

  # a jax.profiler trace of 8 engine steps: device ops beside the
  # engine's serve.* step spans (docs/observability.md)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --requests 4 --max-new 16 --profile-dir /tmp/prof --profile-steps 8

  # async HTTP front-end: NDJSON token streaming on localhost:8080
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \
      --http --port 8080 --metrics /tmp/serve.prom
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.data import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.models import model as MD
from repro.serving import (AsyncServer, QualityProbe, Recorder,
                           SamplingParams, attach_dispatch_hook, load_engine,
                           log, slo_report, summary_table)


def _artifact_kind(path):
    from repro.compiler.artifact import ArtifactError, peek_manifest
    try:
        return peek_manifest(path).get("kind")
    except (ArtifactError, OSError) as e:
        raise SystemExit(f"cannot read artifact {path!r}: {e}")


def _resolve_mesh(args):
    """``--mesh DxM`` → mesh; ``--mesh auto`` reads the artifact manifest."""
    if not args.mesh:
        return None
    if args.mesh != "auto":
        return make_serve_mesh(args.mesh)
    if not args.artifact:
        raise SystemExit("--mesh auto needs --artifact (the manifest records "
                         "the intended mesh)")
    from repro.compiler.artifact import ArtifactError, load_artifact
    try:
        art_path = args.artifact
        if _artifact_kind(art_path) == "bundle":
            art_path = str(Path(art_path) / "target")
        manifest = load_artifact(art_path).manifest
    except (ArtifactError, OSError) as e:
        raise SystemExit(f"--mesh auto: cannot load artifact "
                         f"{args.artifact!r}: {e}")
    want = manifest.get("mesh")
    if not want:
        log("serve", "artifact records no intended mesh; serving unsharded")
        return None
    spec = f"{want['data']}x{want['model']}"
    try:
        mesh = make_serve_mesh(spec)
    except ValueError as e:
        log("serve", f"artifact-recorded mesh unusable ({e}); "
            "serving unsharded")
        return None
    log("serve", f"using artifact-recorded mesh {spec}")
    return mesh


def _cli_prompts(args, cfg):
    """``--prompt`` token lists when given, else ``--requests`` synthetic
    prompts from the deterministic TokenStream."""
    if args.prompt:
        out = []
        for spec in args.prompt:
            try:
                out.append([int(t) for t in spec.replace(",", " ").split()])
            except ValueError:
                raise SystemExit(f"--prompt must be token ids, got {spec!r}")
        return out
    stream = TokenStream(vocab_size=cfg.vocab_size, batch_size=1, seq_len=16)
    return [[int(t) for t in stream.batch(i)["tokens"][0][:8]]
            for i in range(args.requests)]


def drain_profiled(engine, handles, out_dir, n_steps: int) -> list:
    """Step ``engine`` until it drains, writing a ``jax.profiler`` trace
    of ``n_steps`` engine steps under ``out_dir``: the device's ops beside
    the engine's ``serve.*`` step spans.  The trace starts after warm-up:
    at the first step after one that decoded, when the prefill and decode
    programs have compiled.  Returns the finished requests."""
    done, left, tracing = [], n_steps, False
    while engine.has_work:
        if (not tracing and left
                and any(len(h.generated) > 1 for h in handles)):
            jax.profiler.start_trace(out_dir)
            tracing = True
        done.extend(engine.step())
        if tracing:
            left -= 1
            if not left:
                jax.profiler.stop_trace()
                tracing = False
    if tracing:
        jax.profiler.stop_trace()
    if left == n_steps:
        log("serve", "--profile-dir: the run drained during warm-up; no "
            "trace written")
    else:
        log("serve", f"profile of {n_steps - left} engine steps → {out_dir}")
    return done


def _serve_http(engine, args, rec) -> None:
    """Run the asyncio front-end until interrupted, then dump telemetry."""
    server = AsyncServer(engine, host=args.host, port=args.port,
                         rate_limit=args.rate_limit,
                         rate_burst=args.rate_burst)

    async def _run():
        await server.start()
        try:
            await server.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await server.stop()

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        log("serve", "interrupted; shutting down")
    if rec is not None:
        print(summary_table(rec.registry))
        if args.slo_report:
            print(slo_report(rec.slo))
        if args.metrics:
            rec.write_metrics(args.metrics)
            log("serve", f"metrics (Prometheus text format) → {args.metrics}")
        if args.trace_out:
            rec.write_trace(args.trace_out)
            log("serve", f"trace (Chrome trace-event JSON) → "
                f"{args.trace_out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=2,
                    help="decode batch rows (continuous-batching engine); "
                         "also the slot count of the fixed-slot engine")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV-cache page size (tokens per page)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens prefetched per engine step — long "
                         "prompts interleave with decode in chunks this big")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV page-pool size; smaller than "
                         "max_batch*ceil(max_len/page_size) turns on "
                         "eviction (host swap) under pressure")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable radix prefix reuse (paged engine): every "
                         "request prefills from scratch")
    ap.add_argument("--verify-backend", default="auto",
                    choices=("auto", "scan", "fused"),
                    help="speculative verify-window implementation: 'scan' "
                         "replays the window token-by-token (oracle), "
                         "'fused' runs the layer-major fused window; "
                         "'auto' honours REPRO_VERIFY_BACKEND then fused")
    ap.add_argument("--amm", action="store_true",
                    help="serve MLPs through the LUT-MU path")
    ap.add_argument("--amm-backend", default="auto",
                    choices=("auto", "ref", "unfused", "fused"),
                    help="LUT-MU engine backend (kernels.dispatch); "
                         "'auto' picks per shape/dtype/platform")
    ap.add_argument("--artifact",
                    help="amm_lm artifact dir from `python -m repro.compiler "
                         "lm` — serve its compiled LUT-MU tables instead of "
                         "the dense MLPs.  A bundle dir (`... bundle`) "
                         "serves its target half, or both halves with "
                         "--speculative")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-propose / target-verify serving "
                         "(bit-identical greedy streams).  Needs a bundle "
                         "--artifact, or compiles one in-process")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens proposed per verify step (default: "
                         "the bundle manifest's recorded value, else 4)")
    ap.add_argument("--draft-resolution", default="int4",
                    choices=("float32", "int8", "int4"),
                    help="draft LUT width for the in-process bundle compile "
                         "(--speculative without a bundle --artifact)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 (default) = greedy argmax, "
                         "bit-identical to the pre-sampling engines")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the minimal probability "
                         "mass p (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed+i, and a "
                         "rerun with the same seed reproduces every stream "
                         "bit-exactly (any engine, any batch size)")
    ap.add_argument("--mesh",
                    help="serve sharded on a 'DxM' (data x model) mesh, or "
                         "'auto' to use the mesh recorded in the --artifact "
                         "manifest; default: single-device")
    ap.add_argument("--ckpt")
    ap.add_argument("--prompt", action="append", metavar="TOKENS",
                    help="explicit prompt as space/comma-separated token "
                         "ids (repeatable); replaces the synthetic "
                         "TokenStream requests")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP instead of draining a synthetic "
                         "batch: POST /v1/generate streams NDJSON tokens, "
                         "GET /metrics exposes Prometheus text format, "
                         "GET /healthz answers ok (see docs/api.md)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="HTTP bind address (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=8080,
                    help="HTTP port (0 = ephemeral; printed on startup)")
    ap.add_argument("--rate-limit", type=float, default=None, metavar="RPS",
                    help="per-tenant request rate limit (token bucket, "
                         "requests/second; X-Tenant header keys the "
                         "bucket); over-limit requests get 429")
    ap.add_argument("--rate-burst", type=float, default=None,
                    help="token-bucket burst size (default: max(1, "
                         "rate-limit))")
    ap.add_argument("--metrics", metavar="PATH",
                    help="record serving metrics (TTFT/TPOT/ITL histograms, "
                         "pool gauges, speculative acceptance, ...), print "
                         "a summary table, and write a Prometheus "
                         "text-format exposition snapshot to PATH")
    ap.add_argument("--trace-out", metavar="PATH",
                    help="record per-request lifecycle spans and write "
                         "Chrome trace-event JSON to PATH (open in Perfetto "
                         "or chrome://tracing; see docs/observability.md)")
    ap.add_argument("--quality-probe", type=float, default=0.0,
                    metavar="RATE",
                    help="replay this fraction of finished requests through "
                         "the dense reference: per-layer relative-error "
                         "histograms, codebook utilisation and dequant "
                         "saturation (GET /debug/quality; emitted streams "
                         "are untouched — see docs/observability.md)")
    ap.add_argument("--profile-dir", metavar="DIR",
                    help="write a jax.profiler trace of --profile-steps "
                         "engine steps after warm-up under DIR: device ops "
                         "beside the engine's serve.* step spans (read it "
                         "with TensorBoard, Perfetto or "
                         "jax.profiler.ProfileData)")
    ap.add_argument("--profile-steps", type=int, default=8, metavar="N",
                    help="engine steps the --profile-dir trace covers "
                         "(default 8)")
    ap.add_argument("--slo-report", action="store_true",
                    help="print the sliding-window SLO health report "
                         "(tok/s, TTFT/TPOT p50/p99, acceptance, error "
                         "budgets) after serving; live snapshot at GET /slo")
    args = ap.parse_args()
    if args.profile_dir and args.http:
        ap.error("--profile-dir traces a CLI run; drop --http")
    if args.profile_steps < 1:
        ap.error(f"--profile-steps must be >= 1, got {args.profile_steps}")

    mesh = _resolve_mesh(args)

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.amm:
        cfg = dataclasses.replace(
            cfg, amm=dataclasses.replace(cfg.amm, enabled=True,
                                         backend=args.amm_backend))
    key = jax.random.PRNGKey(0)
    dtype = jnp.float32 if args.reduced else jnp.bfloat16
    # --artifact serves compiled tables spliced into a *dense* params tree
    params = MD.init_params(cfg, key, dtype,
                            serving=args.amm and not args.artifact)
    if args.ckpt:
        from repro.checkpoint import restore_into
        template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        params = restore_into(template, Path(args.ckpt))

    art_kind = _artifact_kind(args.artifact) if args.artifact else None
    # one recorder feeds the summary table, the Prometheus snapshot, the
    # Chrome trace and GET /metrics; without the flags engines keep the
    # NullRecorder (zero-overhead-off — see docs/observability.md)
    rec = (Recorder(trace=bool(args.trace_out))
           if (args.metrics or args.trace_out or args.http
               or args.quality_probe or args.slo_report) else None)
    if rec is not None and args.quality_probe:
        # `params` is the pre-splice tree: with a --ckpt/random dense model
        # it still carries the dense mlp weights the probe references
        # (pure-AMM params degrade to utilisation/saturation tracking)
        rec.quality = QualityProbe(rec.registry, rate=args.quality_probe,
                                   dense_params=params)
    if rec is not None:
        attach_dispatch_hook(rec.registry)
    kwargs = dict(max_batch=args.max_batch, max_len=args.max_len,
                  page_size=args.page_size,
                  prefill_chunk=args.prefill_chunk,
                  num_pages=args.num_pages,
                  prefix_cache=not args.no_prefix_cache,
                  verify_backend=args.verify_backend,
                  compute_dtype=dtype, mesh=mesh, recorder=rec)

    if args.speculative:
        if not MD.supports_paged(cfg):
            raise SystemExit("--speculative needs the paged engine (a "
                             "family with paged KV)")
        if mesh is not None:
            raise SystemExit("--speculative serving is single-device for "
                             "now (mesh support is a ROADMAP open item)")
        if args.spec_k is not None:
            kwargs["spec_k"] = args.spec_k
        if art_kind == "bundle":
            engine = load_engine(args.artifact, params, cfg, **kwargs)
        elif art_kind is not None:
            raise SystemExit(
                f"--speculative needs a target+draft bundle artifact, got "
                f"kind {art_kind!r} — compile one with `python -m "
                "repro.compiler bundle`")
        else:
            if args.amm:
                raise SystemExit("--speculative without an artifact "
                                 "calibrates from the dense MLPs — drop "
                                 "--amm (the compiled bundle IS the LUT-MU "
                                 "path)")
            from repro.compiler import compile_lm_bundle
            kwargs.setdefault("spec_k", 4)
            calib = TokenStream(vocab_size=cfg.vocab_size, batch_size=8,
                                seq_len=32)
            log("serve", f"compiling in-process bundle (target=int8, "
                f"draft={args.draft_resolution})…")
            res = compile_lm_bundle(
                params, cfg, calib.batch(0)["tokens"],
                target_resolution="int8",
                draft_resolution=args.draft_resolution,
                spec_k=kwargs["spec_k"])
            engine = load_engine((res.target, res.draft), params, cfg,
                                 **kwargs)
    else:
        # load_engine sniffs artifact vs bundle (a bundle without
        # --speculative serves its full-resolution target half — the
        # stream-defining model and the speculative differential oracle)
        engine = load_engine(args.artifact, params, cfg, speculative=False,
                             **kwargs)

    if args.http:
        _serve_http(engine, args, rec)
        return

    prompts = _cli_prompts(args, cfg)
    # per-request seed: streams stay reproducible (and distinct) however
    # the batch interleaves them
    handles = [engine.submit(prompt, max_new_tokens=args.max_new,
                             sampling=SamplingParams(
                                 temperature=args.temperature,
                                 top_k=args.top_k, top_p=args.top_p,
                                 seed=args.seed + i))
               for i, prompt in enumerate(prompts)]
    t0 = time.time()
    if args.profile_dir:
        done = drain_profiled(engine, handles, args.profile_dir,
                              args.profile_steps)
    else:
        done = engine.run_until_drained()
    dt = time.time() - t0
    n_tok = sum(len(r.generated) for r in done)
    print(f"{len(done)} requests, {n_tok} tokens, {dt:.1f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s)")
    if args.speculative:
        log("spec", f"k={engine.spec_k} rounds={engine.stats['rounds']} "
            f"acceptance={engine.acceptance_rate:.3f} "
            f"tokens/round={engine.mean_emitted_per_round:.2f}")
    if rec is not None:
        print(summary_table(rec.registry))
        if args.slo_report:
            print(slo_report(rec.slo))
        if args.metrics:
            rec.write_metrics(args.metrics)
            log("serve", f"metrics (Prometheus text format) → {args.metrics}")
        if args.trace_out:
            rec.write_trace(args.trace_out)
            log("serve", f"trace (Chrome trace-event JSON) → "
                f"{args.trace_out}")
    for r in done:
        print(f"  req {r.uid}: {r.prompt} → {r.generated}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
