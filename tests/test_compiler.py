"""Offline-compiler tests: artifact round-trip (bit-identical), quantised
parity per resolution config, corruption/version rejection, and the
compile → serve wiring."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import (ARTIFACT_VERSION, ArtifactError, CompileResult,
                            compile_chain, compile_lm_amm, load_artifact)
from repro.core import lut_mu as LM


def _toy_problem(seed=0, d=64, h=64, o=16, n_calib=1024):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(32, d)).astype(np.float32)
    calib = (centers[rng.integers(0, 32, n_calib)]
             + 0.05 * rng.normal(size=(n_calib, d)).astype(np.float32))
    w0 = (rng.normal(size=(d, h)) / np.sqrt(d)).astype(np.float32)
    w1 = (rng.normal(size=(h, o)) / np.sqrt(h)).astype(np.float32)
    b0 = 0.1 * rng.normal(size=(h,)).astype(np.float32)
    b1 = 0.1 * rng.normal(size=(o,)).astype(np.float32)
    return calib, [w0, w1], [b0, b1]


def _compile(calib, ws, bs, resolution="float32", out=None) -> CompileResult:
    return compile_chain(ws, bs, calib, num_codebooks=[8, 8], depths=[4, 4],
                         activations=["relu"], resolution=resolution, out=out)


@pytest.fixture(scope="module")
def toy():
    return _toy_problem()


def test_artifact_roundtrip_bit_identical(toy, tmp_path_factory):
    """compile → save → load → outputs bit-identical to the in-memory chain,
    for the float reference AND every quantised config (stored entries are
    exact in all of them)."""
    calib, ws, bs = toy
    x = jnp.asarray(calib[:64])
    for res in ("float32", "int16", "int8", "int4"):
        out = tmp_path_factory.mktemp("art") / res
        result = _compile(calib, ws, bs, resolution=res, out=str(out))
        loaded = load_artifact(out)
        chain = loaded.to_chain()
        a = np.asarray(result.chain(x))
        b = np.asarray(chain(x))
        assert np.array_equal(a, b), f"{res} round-trip not bit-identical"
        # AMMChain.load is the core-level loader for the same artifact
        c = np.asarray(LM.AMMChain.load(out)(x))
        assert np.array_equal(a, c)


def test_quantised_parity_per_resolution(toy):
    """Every resolution config runs through lutmu_matmul with bounded error
    vs the float chain, and tighter bits ⇒ tighter parity."""
    calib, ws, bs = toy
    x = jnp.asarray(calib[:128])
    ref = np.asarray(_compile(calib, ws, bs, "float32").chain(x))
    ref_norm = np.linalg.norm(ref)
    # intermediate-layer quantisation can flip individual encode decisions
    # (discrete jumps), so the bounds are loose at coarse bits
    tol = {"int16": 1e-3, "int8": 2e-1, "int4": 6e-1}
    errs = {}
    for res, t in tol.items():
        out = np.asarray(_compile(calib, ws, bs, res).chain(x))
        errs[res] = float(np.linalg.norm(out - ref) / ref_norm)
        assert errs[res] < t, (res, errs[res])
    assert errs["int16"] < errs["int4"]


def test_resource_report_shrinks_across_configs(toy):
    calib, ws, bs = toy
    report = _compile(calib, ws, bs).report
    cfgs = report["configs"]
    assert (cfgs["float32"]["pruned_lut_bytes"]
            > cfgs["int16"]["pruned_lut_bytes"]
            > cfgs["int8"]["pruned_lut_bytes"]
            > cfgs["int4"]["pruned_lut_bytes"])
    # pruning itself shrinks every config (chained layer ships I'·C' cols)
    for rec in cfgs.values():
        assert rec["pruned_lut_bytes"] < rec["unpruned_lut_bytes"]
        assert rec["savings_vs_same_config_unpruned"] > 1.0


def test_pruned_chain_matches_unpruned_at_kept_dims(toy):
    """The compiler's pruned hand-off keeps the core losslessness
    invariant: pruned vs prune=False chains agree exactly."""
    calib, ws, bs = toy
    x = jnp.asarray(calib[:64])
    pruned = _compile(calib, ws, bs).chain
    full = compile_chain(ws, bs, calib, num_codebooks=[8, 8], depths=[4, 4],
                         activations=["relu"], prune=False).chain
    np.testing.assert_array_equal(np.asarray(pruned(x)),
                                  np.asarray(full(x)))


def test_manifest_corruption_rejected(toy, tmp_path):
    calib, ws, bs = toy
    out = tmp_path / "art"
    _compile(calib, ws, bs, out=str(out))

    # tensor corruption → checksum mismatch
    with open(out / "tensors.npz", "ab") as f:
        f.write(b"\x00garbage")
    with pytest.raises(ArtifactError, match="checksum"):
        load_artifact(out)


def test_version_and_format_mismatch_rejected(toy, tmp_path):
    calib, ws, bs = toy
    out = tmp_path / "art"
    _compile(calib, ws, bs, out=str(out))
    mf = out / "manifest.json"
    manifest = json.loads(mf.read_text())

    bad = dict(manifest, version=ARTIFACT_VERSION + 1)
    mf.write_text(json.dumps(bad))
    with pytest.raises(ArtifactError, match="version"):
        load_artifact(out)

    bad = dict(manifest, format="something-else")
    mf.write_text(json.dumps(bad))
    with pytest.raises(ArtifactError, match="format"):
        load_artifact(out)

    mf.write_text("{not json")
    with pytest.raises(ArtifactError, match="corrupt manifest"):
        load_artifact(out)

    mf.unlink()
    with pytest.raises(ArtifactError, match="manifest"):
        load_artifact(out)


def test_missing_tensor_rejected(toy, tmp_path):
    calib, ws, bs = toy
    out = tmp_path / "art"
    result = _compile(calib, ws, bs, out=str(out))
    tensors = {k: v for k, v in result.artifact.tensors.items()
               if k != "layer1/lut"}
    np.savez_compressed(out / "tensors.npz", **tensors)
    manifest = json.loads((out / "manifest.json").read_text())
    from repro.compiler.artifact import _sha256
    manifest["tensors_sha256"] = _sha256(out / "tensors.npz")
    (out / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(ArtifactError, match="layer1/lut"):
        load_artifact(out)


def test_planner_records_backend_and_pruning(toy):
    calib, ws, bs = toy
    result = _compile(calib, ws, bs, "int8")
    recs = result.artifact.manifest["layers"]
    assert recs[0]["pruned"] and not recs[1]["pruned"]
    assert recs[0]["cols"] == recs[0]["depth"] * recs[1]["num_codebooks"]
    for rec in recs:
        assert rec["backend"] in ("ref", "unfused", "fused")
    # on this host the recorded backends drive the chain's auto dispatch
    assert result.chain.backends == tuple(r["backend"] for r in recs)


def test_lm_artifact_serves(tmp_path):
    """compile_lm_amm → load_engine(artifact dir) completes requests."""
    from repro.configs import get_config
    from repro.models import model as MD
    from repro.serving import ServeEngine, load_engine

    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=64, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True,
                                     quantize_int8=False))
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(0, 64, (4, 16))
    out = tmp_path / "lm_art"
    compile_lm_amm(params, cfg, tokens, out=str(out))

    eng = load_engine(out, params, cfg, max_batch=2, max_len=64)
    assert isinstance(eng, ServeEngine)
    reqs = [eng.submit([1, 2, 3], max_new_tokens=4) for _ in range(3)]
    done = eng.run_until_drained()
    assert len(done) == 3
    assert all(len(r.generated) == 4 for r in reqs)

    # arch mismatch is rejected
    other = dataclasses.replace(cfg, name="not-this-arch")
    with pytest.raises(ArtifactError, match="arch"):
        load_engine(out, params, other)
    # same arch name but different geometry (reduced vs full) is rejected
    bigger = dataclasses.replace(cfg, num_layers=cfg.num_layers + 2)
    with pytest.raises(ArtifactError, match="layers"):
        load_engine(out, params, bigger)


def test_cli_compile_verify(tmp_path):
    """`python -m repro.compiler mlp --verify` round-trips an artifact."""
    from repro.compiler.__main__ import main

    out = tmp_path / "cli_art"
    rc = main(["mlp", "--sizes", "784", "32", "10", "--samples", "512",
               "--calib", "256", "--train-steps", "20",
               "--resolution", "int8", "--out", str(out), "--verify"])
    assert rc == 0
    assert (out / "manifest.json").is_file()
    assert main(["inspect", str(out)]) == 0
    assert main(["verify", str(out)]) == 0
