"""CLI for the offline LUT-MU compiler.

Usage:
  # compile the demo MLP (synthetic MNIST) to a servable artifact
  PYTHONPATH=src python -m repro.compiler mlp --out artifacts/mlp_int8 \
      --resolution int8 --verify

  # compile a (trained or randomly-initialised) LM's MLP blocks
  PYTHONPATH=src python -m repro.compiler lm --arch qwen3-14b --reduced \
      --out artifacts/qwen_amm [--ckpt CKPT_DIR]

  # inspect / verify an existing artifact
  PYTHONPATH=src python -m repro.compiler inspect artifacts/mlp_int8
  PYTHONPATH=src python -m repro.compiler verify artifacts/mlp_int8
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _print_report(report: dict) -> None:
    print("resource report (total LUT bytes):")
    print(f"  {'config':>8}  {'pruned':>12}  {'unpruned':>12}  "
          f"{'vs f32 unpruned':>15}")
    for name, rec in report.get("configs", {}).items():
        print(f"  {name:>8}  {rec['pruned_lut_bytes']:>12}  "
              f"{rec['unpruned_lut_bytes']:>12}  "
              f"{rec['savings_vs_float32_unpruned']:>14.2f}x")


def cmd_mlp(args) -> int:
    from repro.compiler import compile_chain, load_artifact
    from repro.data import synthetic_mnist
    from repro.models import cnn

    if args.verify and not args.out:
        print("--verify needs --out (nothing to reload otherwise)",
              file=sys.stderr)
        return 2
    x, y = synthetic_mnist(args.samples, seed=1)
    cfg = cnn.MLPConfig(sizes=tuple(args.sizes))
    n_layers = len(cfg.sizes) - 1
    print(f"[compiler] training exact MLP {cfg.sizes} "
          f"({args.train_steps} steps)…")
    params = cnn.mlp_train(cfg, x, y, steps=args.train_steps, lr=0.1)
    weights = [np.asarray(params[f"w{i}"]) for i in range(n_layers)]
    biases = [np.asarray(params[f"b{i}"]) for i in range(n_layers)]
    nc = args.num_codebooks or [max(1, s // 8) for s in cfg.sizes[:-1]]
    if len(nc) != n_layers:
        print(f"--num-codebooks needs {n_layers} values", file=sys.stderr)
        return 2
    print(f"[compiler] calibrating on {args.calib} samples, "
          f"resolution={args.resolution}…")
    result = compile_chain(
        weights, biases, x[:args.calib],
        num_codebooks=nc, depths=[args.depth] * n_layers,
        activations=["relu"] * (n_layers - 1),
        resolution=args.resolution, prune=not args.no_prune,
        autotune=args.autotune, name="mlp-demo", out=args.out)
    _print_report(result.report)
    acc = cnn.mlp_accuracy(lambda xb: result.chain(xb), x[:512], y[:512])
    exact = cnn.mlp_accuracy(
        lambda xb: cnn.mlp_forward(params, xb, n_layers), x[:512], y[:512])
    print(f"[compiler] accuracy: exact={exact:.3f} compiled={acc:.3f}")
    if args.out:
        print(f"[compiler] wrote artifact → {result.path}")
        if args.verify:
            chain = load_artifact(result.path).to_chain()
            a = np.asarray(result.chain(jnp.asarray(x[:64])))
            b = np.asarray(chain(jnp.asarray(x[:64])))
            ok = np.array_equal(a, b)
            print(f"[compiler] round-trip bit-identical: {ok}")
            return 0 if ok else 1
    return 0


def _lm_setup(args):
    """Shared ``lm`` / ``bundle`` preamble → (cfg, params, tokens,
    mesh_shape) or an error string."""
    import dataclasses

    from repro.configs import get_config
    from repro.data import TokenStream
    from repro.models import model as MD

    cfg = get_config(args.arch, reduced=args.reduced)
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True))
    params = MD.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    if args.ckpt:
        from pathlib import Path

        from repro.checkpoint import restore_into
        template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        params = restore_into(template, Path(args.ckpt))
    ts = TokenStream(vocab_size=cfg.vocab_size, batch_size=args.calib_batch,
                     seq_len=args.calib_seq)
    tokens = np.asarray(ts.batch(0)["tokens"])
    mesh_shape = None
    if getattr(args, "mesh", None):
        from repro.launch.mesh import parse_mesh_spec
        try:
            data, model = parse_mesh_spec(args.mesh)
        except ValueError as e:
            return None, f"--mesh: {e}"
        mesh_shape = {"data": data, "model": model}
    return (cfg, params, tokens, mesh_shape), None


def cmd_lm(args) -> int:
    from repro.compiler import compile_lm_amm

    setup, err = _lm_setup(args)
    if err:
        print(err, file=sys.stderr)
        return 2
    cfg, params, tokens, mesh_shape = setup
    resolution = args.resolution
    if args.float_luts:  # back-compat alias for the pre-resolution flag
        if resolution is not None and resolution != "float32":
            print("--float-luts contradicts --resolution "
                  f"{resolution} — pick one", file=sys.stderr)
            return 2
        resolution = "float32"
    if resolution is None:
        resolution = "int8"
    print(f"[compiler] capturing MLP inputs for {cfg.num_layers} layers…")
    result = compile_lm_amm(params, cfg, tokens, out=args.out,
                            mesh_shape=mesh_shape, resolution=resolution)
    print(f"[compiler] amm_lm artifact ({result.artifact.resolution}): "
          f"{result.report['lut_bytes']} LUT bytes → "
          f"{result.path or '(not saved)'}")
    return 0


def cmd_bundle(args) -> int:
    from repro.compiler import compile_lm_bundle

    setup, err = _lm_setup(args)
    if err:
        print(err, file=sys.stderr)
        return 2
    cfg, params, tokens, mesh_shape = setup
    print(f"[compiler] one calibration pass for {cfg.num_layers} layers, "
          f"baking target={args.target_resolution} + "
          f"draft={args.draft_resolution}…")
    result = compile_lm_bundle(
        params, cfg, tokens, out=args.out, mesh_shape=mesh_shape,
        target_resolution=args.target_resolution,
        draft_resolution=args.draft_resolution, spec_k=args.spec_k)
    r = result.report
    print(f"[compiler] bundle: target {r['target']['lut_bytes']} LUT bytes "
          f"({r['target']['resolution']}), draft {r['draft']['lut_bytes']} "
          f"({r['draft']['resolution']}), draft ships "
          f"{r['draft_vs_target_stored']:.2f}x smaller → "
          f"{result.path or '(not saved)'}")
    return 0


def cmd_inspect(args) -> int:
    from repro.compiler import load_artifact, peek_manifest

    if peek_manifest(args.path).get("kind") == "bundle":
        from repro.compiler import load_bundle

        _, _, manifest = load_bundle(args.path)
        print(json.dumps(manifest, indent=2))
        return 0
    art = load_artifact(args.path)
    m = dict(art.manifest)
    m.pop("resource_report", None)
    print(json.dumps(m, indent=2))
    _print_report(art.resource_report)
    return 0


def cmd_verify(args) -> int:
    from repro.compiler import load_artifact, peek_manifest

    if peek_manifest(args.path).get("kind") == "bundle":
        from repro.compiler import load_bundle

        target, draft, _ = load_bundle(args.path)  # full validation
        print(f"[compiler] {args.path}: bundle "
              f"(target={target.resolution}, draft={draft.resolution}) — "
              "manifests/checksums OK")
        return 0
    art = load_artifact(args.path)  # checksum + schema validation happens here
    print(f"[compiler] {args.path}: kind={art.kind} "
          f"resolution={art.resolution} — manifest/checksum OK")
    if art.kind == "amm_chain":
        chain = art.to_chain()
        d = art.manifest["layers"][0]["in_features"]
        x = jnp.asarray(np.random.default_rng(0).normal(size=(16, d)),
                        jnp.float32)
        out = chain(x)
        finite = bool(jnp.all(jnp.isfinite(out)))
        print(f"[compiler] forward smoke: out shape {tuple(out.shape)}, "
              f"finite={finite}")
        return 0 if finite else 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.compiler")
    sub = ap.add_subparsers(dest="cmd", required=True)

    mlp = sub.add_parser("mlp", help="compile the demo MLP")
    mlp.add_argument("--sizes", type=int, nargs="+",
                     default=[784, 128, 128, 10])
    mlp.add_argument("--samples", type=int, default=2048)
    mlp.add_argument("--calib", type=int, default=1024)
    mlp.add_argument("--train-steps", type=int, default=250)
    mlp.add_argument("--num-codebooks", type=int, nargs="+", default=None)
    mlp.add_argument("--depth", type=int, default=4)
    mlp.add_argument("--resolution", default="float32",
                     choices=("float32", "int16", "int8", "int4"))
    mlp.add_argument("--no-prune", action="store_true")
    mlp.add_argument("--autotune", action="store_true")
    mlp.add_argument("--out")
    mlp.add_argument("--verify", action="store_true",
                     help="reload the artifact and check bit-identity")
    mlp.set_defaults(fn=cmd_mlp)

    lm = sub.add_parser("lm", help="compile an LM's MLP blocks (amm_lm)")
    lm.add_argument("--arch", required=True)
    lm.add_argument("--reduced", action="store_true")
    lm.add_argument("--ckpt")
    lm.add_argument("--calib-batch", type=int, default=8)
    lm.add_argument("--calib-seq", type=int, default=32)
    lm.add_argument("--resolution", default=None,
                    choices=("float32", "int8", "int4"),
                    help="LUT entry width baked into the artifact "
                         "(default int8)")
    lm.add_argument("--float-luts", action="store_true",
                    help="deprecated alias of --resolution float32")
    lm.add_argument("--mesh",
                    help="intended serving mesh 'DxM' (data x model), "
                         "recorded in the manifest for --mesh auto serving")
    lm.add_argument("--out")
    lm.set_defaults(fn=cmd_lm)

    bd = sub.add_parser(
        "bundle",
        help="compile a target+draft artifact pair for speculative decoding")
    bd.add_argument("--arch", required=True)
    bd.add_argument("--reduced", action="store_true")
    bd.add_argument("--ckpt")
    bd.add_argument("--calib-batch", type=int, default=8)
    bd.add_argument("--calib-seq", type=int, default=32)
    bd.add_argument("--target-resolution", default="int8",
                    choices=("float32", "int8", "int4"),
                    help="verifier LUT width (defines the served streams)")
    bd.add_argument("--draft-resolution", default="int4",
                    choices=("float32", "int8", "int4"),
                    help="proposer LUT width (cheaper = the throughput win)")
    bd.add_argument("--spec-k", type=int, default=4,
                    help="suggested draft tokens per verify step, recorded "
                         "in the bundle manifest")
    bd.add_argument("--mesh",
                    help="intended serving mesh, recorded in both halves")
    bd.add_argument("--out")
    bd.set_defaults(fn=cmd_bundle)

    ins = sub.add_parser("inspect", help="print an artifact's manifest")
    ins.add_argument("path")
    ins.set_defaults(fn=cmd_inspect)

    ver = sub.add_parser("verify", help="validate + smoke-run an artifact")
    ver.add_argument("path")
    ver.set_defaults(fn=cmd_verify)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
