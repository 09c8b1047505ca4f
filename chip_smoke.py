"""Chip smoke test: serve qwen3-14b at published widths on a TPU.

Drives the main serving path through the entry points a user calls
(``load_engine``, ``ServeEngine.submit``/``run_until_drained`` and the
``AsyncServer`` HTTP front end) on qwen3-14b at its published widths, with
LUT-MU MLPs (random int8 tables, ``d_sub=8, depth=4``, chain pruning on)
and bf16 everywhere else.  Only the depth is cut, to 16 of 40 layers, so
the weights fit one 16 GB chip.  Weights are random, made from ``--seed``.

Phases (one process; nothing here starts a child):

  a. build the engine, compile its prefill and decode programs (both must
     contain a ``tpu_custom_call`` per LUT-MU site), and serve 8 requests
     of 16..1500 prompt tokens and 16 new tokens each at ``max_batch=8``;
  b. compare one 256-token prefill's logits against the same params run
     with the pure-jnp ``ref`` LUT-MU backend;
  c. answer 2 NDJSON requests over HTTP on an ephemeral port, and check
     that they stream the tokens the offline engine produced.

``--four-chips`` runs only the sharded path instead: the 16-layer model on
one chip and on a ``1x4`` (data x model) mesh with the same requests, its
LUT-MU sites on the mesh against one chip, a dense-MLP twin on the mesh
against one chip, then all 40 layers on the mesh.

The script exits non-zero, and prints no result, when JAX finds no TPU.
Its last line is ``{"ok": true, "device": {...}}``.

Usage::

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-14b"
DEPTH = 16                 # layers kept on one chip (published: 40)
PROMPT_LENS = (16, 64, 200, 333, 512, 800, 1111, 1500)
MAX_NEW = 16
MAX_BATCH = 8
MAX_LEN = 2048
PAGE_SIZE = 16
PREFILL_CHUNK = 256
HTTP_REQUESTS = (0, 3)     # indices into the phase-a prompts
REF_TOKENS = 256
# Phase b: the int8 tables accumulate exactly in int32 under both
# backends, so the logits differ only where XLA fuses the float code
# around them differently (and through any encode decision such a
# 1-ulp difference flips).
REF_MAX_ABS_FRAC = 1e-2    # max |fused - ref| / max |ref|
REF_ARGMAX_MIN = 0.98      # share of positions with the same argmax
# --four-chips.  The sharded attention output projection sums its heads'
# partials across chips in another order than one chip does, so its bf16
# activations differ in the last bit.  A random LUT-MU model is chaotic
# under that: a tree comparison near its threshold flips, swapping a whole
# LUT row (a 2**-8 relative nudge of one MLP's input moves its output by
# about a sixth on average), so the served model's tokens and logits are
# reported, not held to a tolerance.  What is held:
#   - each LUT-MU site of the mesh (Pallas kernels per shard + psum) against
#     the same tables on one chip: the int8 tables aggregate exactly, so
#     only the float epilogue may round differently;
#   - a dense-MLP twin (no encode to flip) on the mesh against one chip:
#     bf16 drift of the first prefill's logits, 1.3e-2 after 6 layers on
#     the CPU backend, against an O(1) error for a wrong sharding.
LUTMU_MESH_MAX_FRAC = 1e-6  # max |mesh - one chip| / max |one chip|
DENSE_DEPTH = 8             # layers of the dense twin (fits one chip)
DENSE_MESH_MAX_FRAC = 5e-2  # max |mesh - one chip| / max |one chip|


class SmokeError(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def say(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", flush=True)


def model_config(depth: int, *, backend: str = "auto", amm: bool = True):
    from repro.configs import get_config

    cfg = get_config(ARCH)
    return dataclasses.replace(
        cfg, num_layers=depth,
        amm=dataclasses.replace(cfg.amm, enabled=amm, backend=backend))


def init_params(cfg, seed: int, sharding):
    """Random serving params, made on the device (bf16 dense weights,
    int8 LUT-MU tables).  ``sharding`` is one sharding, or a function from
    the params' shapes to a pytree of shardings."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as MD

    def make(key):
        return MD.init_params(cfg, key, jnp.bfloat16, serving=True)

    if not isinstance(sharding, jax.sharding.Sharding):
        sharding = sharding(jax.eval_shape(make, jax.random.PRNGKey(seed)))
    return jax.jit(make, out_shardings=sharding)(jax.random.PRNGKey(seed))


def tree_bytes(tree) -> int:
    import jax

    return sum(a.nbytes for a in jax.tree.leaves(tree))


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def make_prompts(cfg, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in PROMPT_LENS]


def build_engine(params, cfg, mesh=None):
    import jax.numpy as jnp

    from repro.serving import load_engine

    return load_engine(None, params, cfg, max_batch=MAX_BATCH,
                       max_len=MAX_LEN, page_size=PAGE_SIZE,
                       prefill_chunk=PREFILL_CHUNK,
                       compute_dtype=jnp.bfloat16, mesh=mesh)


def compile_programs(eng) -> None:
    """AOT-compile the engine's prefill and decode programs, timing each,
    and check every LUT-MU site of the layer scan became a Pallas kernel."""
    import jax
    import jax.numpy as jnp

    mp = eng.max_pages_per_seq
    args = {
        "prefill": (eng._prefill, (
            eng.params, jnp.zeros((1, eng.prefill_chunk), jnp.int32),
            jnp.int32(0), jnp.int32(eng.prefill_chunk),
            jnp.zeros((mp,), jnp.int32), eng.kv.buffers)),
        "decode": (eng._decode, (
            eng.params, jnp.zeros((eng.max_batch, 1), jnp.int32),
            jnp.zeros((eng.max_batch,), jnp.int32),
            jnp.full((eng.max_batch, mp), eng.kv.trash, jnp.int32),
            eng.kv.buffers)),
    }
    for name, (fn, a) in args.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*a).compile()
        secs = time.perf_counter() - t0
        kernels = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        mem = compiled.memory_analysis()
        say("compile", f"{name}: {secs:.2f} s, {kernels} tpu_custom_call, "
            f"temp {gib(mem.temp_size_in_bytes)}, "
            f"args {gib(mem.argument_size_in_bytes)}")
        # gate, up and down each run one kernel inside the layer scan
        check(kernels >= 3, f"{name} program has {kernels} tpu_custom_call "
              "(want >= 3: a LUT-MU site gave way to ref or interpret mode)")


class DispatchLog:
    """Records the backend every LUT-MU site resolved to (the dispatch
    hook fires once per traced site)."""

    def __init__(self):
        self.sites = []

    def __call__(self, **meta) -> None:
        self.sites.append(meta)

    def report(self, want_pallas_rows) -> None:
        seen = sorted({(m["b"], m["c"], m["n"], m["input_kind"],
                        m["backend"]) for m in self.sites})
        for b, c, n, kind, be in seen:
            say("lutmu", f"B={b} C={c} N={n} {kind}: {be}")
        for b in want_pallas_rows:
            bes = {m["backend"] for m in self.sites if m["b"] == b}
            check(bes and "ref" not in bes,
                  f"LUT-MU sites at B={b} ran {sorted(bes)}, want Pallas")


def serve(eng, prompts, cfg) -> list:
    handles = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run_until_drained()
    secs = time.perf_counter() - t0
    check(len(done) == len(prompts),
          f"{len(done)} of {len(prompts)} requests drained")
    for h, p in zip(handles, prompts):
        toks = h.tokens()
        check(len(toks) == MAX_NEW,
              f"request with a {len(p)}-token prompt made {len(toks)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"token out of range: {toks}")
        say("serve", f"prompt {len(p):5d} tokens -> {len(toks)} new: "
            f"{toks[:6]}...")
    n_tok = sum(len(h.tokens()) for h in handles)
    say("serve", f"{len(done)} requests drained, {n_tok} tokens in "
        f"{secs:.2f} s (one run, compiles included)")
    return [h.tokens() for h in handles]


def logits_fn(cfg, mesh=None):
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import make_constrainer
    from repro.models import model as MD

    kw = {} if mesh is None else {"constrain": make_constrainer(cfg, mesh)}
    return jax.jit(lambda p, t: MD.forward(
        p, t, cfg, remat=False, compute_dtype=jnp.bfloat16, **kw))


def compare_to_ref(params, cfg, tokens) -> None:
    """Phase b: the served LUT-MU backend vs the pure-jnp ``ref``."""
    import jax.numpy as jnp

    toks = jnp.asarray(tokens[:REF_TOKENS], jnp.int32)[None]
    got = np.asarray(logits_fn(cfg)(params, toks))[0]
    ref_cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, backend="ref"))
    want = np.asarray(logits_fn(ref_cfg)(params, toks))[0]
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite logits")
    diff = float(np.abs(got - want).max())
    frac = diff / float(np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say("ref", f"{toks.shape[1]} positions x {got.shape[-1]} logits: max "
        f"abs diff {diff:.6g} ({frac:.3g} of max |ref|, limit "
        f"{REF_MAX_ABS_FRAC}), argmax agreement {agree:.4f} (limit "
        f"{REF_ARGMAX_MIN})")
    check(frac <= REF_MAX_ABS_FRAC and agree >= REF_ARGMAX_MIN,
          "LUT-MU Pallas path disagrees with the ref backend")


async def _http_generate(port: int, prompt, max_new: int) -> list:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps({"prompt": prompt,
                           "max_new_tokens": max_new}).encode()
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: smoke\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        check(status == 200, f"HTTP status {status}")
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass  # headers: the body is chunked NDJSON
        data = b""
        while True:
            n = int((await reader.readline()).strip() or b"0", 16)
            if n == 0:
                break
            data += await reader.readexactly(n)
            await reader.readline()
    finally:
        writer.close()
        await writer.wait_closed()
    recs = [json.loads(line) for line in data.decode().splitlines()]
    check(recs and recs[-1].get("done") is True, "stream has no done record")
    streamed = [r["token"] for r in recs[:-1]]
    check(streamed == recs[-1]["tokens"],
          "streamed tokens differ from the final record")
    return streamed


def serve_http(eng, prompts, offline) -> None:
    """Phase c: two concurrent NDJSON streams through ``AsyncServer``."""
    from repro.serving import AsyncServer

    async def run():
        server = AsyncServer(eng, port=0)
        await server.start()
        try:
            return await asyncio.gather(*[
                _http_generate(server.port, prompts[i], MAX_NEW)
                for i in HTTP_REQUESTS])
        finally:
            await server.stop()

    got = asyncio.run(run())
    for i, toks in zip(HTTP_REQUESTS, got):
        say("http", f"prompt {len(prompts[i])} tokens -> {len(toks)} "
            f"streamed, same as offline: {toks == offline[i]}")
        check(toks == offline[i], "HTTP stream differs from the offline "
              "engine's tokens for the same prompt")
    say("http", f"{len(got)} requests answered over HTTP")


def report_memory(dev, params, eng) -> None:
    stats = dev.memory_stats() or {}
    say("memory", f"params {gib(tree_bytes(params))}, KV pool "
        f"{gib(tree_bytes(eng.kv.buffers))}, bytes_in_use "
        f"{gib(stats.get('bytes_in_use', 0))}, peak_bytes_in_use "
        f"{gib(stats.get('peak_bytes_in_use', 0))}")


def one_chip(seed: int, dev) -> None:
    import jax

    from repro.kernels import dispatch as D

    cfg = model_config(DEPTH)
    say("config", f"{cfg.name}: d_model {cfg.d_model}, {cfg.num_heads} q / "
        f"{cfg.num_kv_heads} kv heads x {cfg.resolved_head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; LUT-MU MLPs d_sub "
        f"{cfg.amm.d_sub} depth {cfg.amm.depth} prune {cfg.amm.prune} int8;"
        f" bf16 elsewhere")
    say("config", f"depth cut: {DEPTH} of 40 layers (the only cut); seed "
        f"{seed}")
    t0 = time.perf_counter()
    params = init_params(cfg, seed, jax.sharding.SingleDeviceSharding(dev))
    jax.block_until_ready(params)
    say("init", f"params {gib(tree_bytes(params))} on {dev.device_kind} in "
        f"{time.perf_counter() - t0:.2f} s")

    log = DispatchLog()
    D.set_profile_hook(log)
    try:
        # a. build and serve
        eng = build_engine(params, cfg)
        compile_programs(eng)
        prompts = make_prompts(cfg, seed)
        offline = serve(eng, prompts, cfg)
        log.report(want_pallas_rows=(PREFILL_CHUNK, MAX_BATCH))
        report_memory(dev, params, eng)
        # b. the reference comparison
        compare_to_ref(params, cfg, prompts[-1])
    finally:
        D.set_profile_hook(None)
    # c. HTTP
    serve_http(eng, prompts, offline)
    report_memory(dev, params, eng)


def max_frac(got, want) -> float:
    return float(np.abs(got - want).max()) / float(np.abs(want).max())


def lutmu_mesh_parity(params, mesh, dev, seed: int) -> None:
    """Layer 0's LUT-MU sites through ``lutmu_matmul_sharded`` on the mesh
    vs ``lutmu_matmul`` on one chip, same tables, same inputs."""
    import jax

    from repro.kernels import dispatch as D

    amm = jax.tree.map(lambda a: a[0], params["layers"]["amm_mlp"])
    on_dev = jax.sharding.SingleDeviceSharding(dev)
    rng = np.random.default_rng(seed)
    for site, tree, kind in (("gate", "up", "split"), ("up", "up", "split"),
                             ("down", "down", "package")):
        arrays = (amm[f"{tree}_split_dims"], amm[f"{tree}_thresholds"],
                  amm[f"lut_{site}"], amm[f"lut_{site}_scale"],
                  amm[f"lut_{site}_offset"])
        c, depth = arrays[0].shape
        shape = (PREFILL_CHUNK, c, depth) if kind == "split" else (
            PREFILL_CHUNK, c * depth)
        x = rng.standard_normal(shape, np.float32)
        got = np.asarray(jax.jit(lambda v, *a: D.lutmu_matmul_sharded(
            v, D.params_from_arrays(*a), mesh=mesh, axis="model",
            input_kind=kind))(x, *arrays))
        want = np.asarray(jax.jit(lambda v, *a: D.lutmu_matmul(
            v, D.params_from_arrays(*a), input_kind=kind))(
                jax.device_put(x, on_dev), *jax.device_put(arrays, on_dev)))
        frac = max_frac(got, want)
        say("mesh", f"LUT-MU {site} ({kind}, B={PREFILL_CHUNK} C={c}): 1x4 "
            f"vs one chip max abs diff {frac:.3g} of max |one chip| (limit "
            f"{LUTMU_MESH_MAX_FRAC})")
        check(frac <= LUTMU_MESH_MAX_FRAC,
              f"sharded LUT-MU {site} disagrees with one chip")


def dense_twin_parity(seed: int, mesh, dev, toks) -> None:
    """The dense-MLP twin's first prefill: 1x4 mesh vs one chip."""
    import jax

    from repro.distributed.sharding import param_shardings

    cfg = model_config(DENSE_DEPTH, amm=False)
    params = init_params(cfg, seed, jax.sharding.SingleDeviceSharding(dev))
    want = np.asarray(logits_fn(cfg)(params, toks))[0]
    del params
    gc.collect()
    params = init_params(cfg, seed, lambda s: param_shardings(s, cfg, mesh))
    got = np.asarray(logits_fn(cfg, mesh)(params, toks))[0]
    frac = max_frac(got, want)
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say("mesh", f"dense-MLP twin, {DENSE_DEPTH} layers: first prefill "
        f"logits max abs diff {frac:.3g} of max |one chip| (limit "
        f"{DENSE_MESH_MAX_FRAC}), argmax agreement {agree:.4f}")
    check(frac <= DENSE_MESH_MAX_FRAC,
          "dense twin on the 1x4 mesh disagrees with one chip")


def four_chips(seed: int) -> None:
    """The 16-layer model on one chip vs a 1x4 mesh, then 40 layers on
    the mesh."""
    import jax

    from repro.distributed.sharding import param_shardings
    from repro.launch.mesh import make_mesh

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = model_config(DEPTH)
    prompts = make_prompts(cfg, seed)
    toks = np.asarray(prompts[-1][:REF_TOKENS], np.int32)[None]

    # one chip
    params = init_params(cfg, seed,
                         jax.sharding.SingleDeviceSharding(devs[0]))
    eng = build_engine(params, cfg)
    single = serve(eng, prompts, cfg)
    want = np.asarray(logits_fn(cfg)(params, toks))[0]
    del eng, params
    gc.collect()

    # the same model on a 1x4 mesh
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    say("mesh", f"1x4 (data x model) over {devs[0].device_kind}")
    params = init_params(cfg, seed,
                         lambda s: param_shardings(s, cfg, mesh))
    eng = build_engine(params, cfg, mesh)
    sharded = serve(eng, prompts, cfg)
    got = np.asarray(logits_fn(cfg, mesh)(params, toks))[0]
    same = sum(a == b for a, b in zip(single, sharded))
    say("mesh", f"LUT-MU model: greedy streams identical for {same} of "
        f"{len(single)} requests; first prefill logits max abs diff "
        f"{max_frac(got, want):.3g} of max |one chip|, argmax agreement "
        f"{float((got.argmax(-1) == want.argmax(-1)).mean()):.4f} "
        "(reported, not held: see LUTMU_MESH_MAX_FRAC)")
    lutmu_mesh_parity(params, mesh, devs[0], seed)
    del eng, params
    gc.collect()
    dense_twin_parity(seed, mesh, devs[0], toks)
    gc.collect()

    # all 40 layers on the mesh
    cfg40 = model_config(40)
    t0 = time.perf_counter()
    params = init_params(cfg40, seed,
                         lambda s: param_shardings(s, cfg40, mesh))
    jax.block_until_ready(params)
    say("mesh", f"40 layers: params {gib(tree_bytes(params))} over 4 chips "
        f"in {time.perf_counter() - t0:.2f} s")
    eng = build_engine(params, cfg40, mesh)
    serve(eng, prompts, cfg40)
    for d in devs[:4]:
        stats = d.memory_stats() or {}
        say("memory", f"device {d.id}: bytes_in_use "
            f"{gib(stats.get('bytes_in_use', 0))}, peak_bytes_in_use "
            f"{gib(stats.get('peak_bytes_in_use', 0))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4-mesh path and its one-device "
                         "comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[smoke] no TPU found: JAX's devices are "
              f"{[d.platform for d in devs]}", file=sys.stderr)
        return 1
    say("device", f"{devs[0].device_kind} x {len(devs)}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed, devs[0])
    except SmokeError as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    say("smoke", f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
