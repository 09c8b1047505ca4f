"""End-to-end behaviour tests: the full paper pipeline at reduced scale.

Train a quantised base model → offline-fit LUT-MU → deploy in the serving
engine → verify accuracy/throughput accounting — the complete story of the
paper in one test module.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import lut_mu as LM
from repro.data import TokenStream, synthetic_mnist
from repro.models import cnn
from repro.models.amm_mlp import amm_mlp_apply, fit_from_dense
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.serving import ServeEngine


def test_paper_pipeline_mlp_end_to_end():
    """MNIST-MLP: train exact → swap every matmul for pruned LUT-MUs →
    accuracy within tolerance, footprint reduced ~2x (the paper's headline)."""
    x, y = synthetic_mnist(2048, seed=1)
    cfg = cnn.MLPConfig(sizes=(784, 128, 128, 10))
    params = cnn.mlp_train(cfg, x, y, steps=250, lr=0.1)
    n_layers = len(cfg.sizes) - 1
    exact_acc = cnn.mlp_accuracy(
        lambda xb: cnn.mlp_forward(params, xb, n_layers), x[:512], y[:512])
    weights = [np.asarray(params[f"w{i}"]) for i in range(n_layers)]
    biases = [np.asarray(params[f"b{i}"]) for i in range(n_layers)]

    # high resolution (I/d_sub = 4/4): accuracy preserved (paper Fig. 11's
    # upper-right corner)
    hi = cnn.mlp_to_amm(params, cfg, x[:1024], num_codebooks=(98, 32, 32),
                        depths=(4, 4, 4))
    hi_acc = cnn.mlp_accuracy(lambda xb: hi(xb), x[:512], y[:512])
    assert hi_acc > exact_acc - 0.1, (exact_acc, hi_acc)

    # the paper's default resolution (4/8): moderate accuracy impact traded
    # for the headline ~50 % parameter pruning on the chained layers
    lo = cnn.mlp_to_amm(params, cfg, x[:1024], num_codebooks=(98, 16, 16),
                        depths=(4, 4, 4))
    lo_acc = cnn.mlp_accuracy(lambda xb: lo(xb), x[:512], y[:512])
    unpruned = LM.unpruned_chain(lo, weights, biases)
    assert lo_acc > 0.3  # well above 10-class chance, below exact
    assert lo.lut_bytes() < 0.7 * unpruned.lut_bytes()


def test_lm_train_then_serve_with_amm():
    """Tiny LM: train on the token stream, fit AMM-MLP params from live
    activations, and serve through the engine with the LUT-MU path on."""
    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=128, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    import tempfile
    ts = TokenStream(vocab_size=cfg.vocab_size, batch_size=8, seq_len=32)
    tr = Trainer(cfg, TrainerConfig(tempfile.mkdtemp(), ckpt_every=100,
                                    lr=3e-3, warmup_steps=5,
                                    compute_dtype=jnp.float32),
                 lambda s: ts.batch(s))
    out = tr.run(25)
    assert out["losses"][-1] < out["losses"][0]
    params = tr.state.params

    # serve exact
    eng = ServeEngine(params, cfg, max_batch=2, max_len=64)
    reqs = [eng.submit([1, 2, 3, 4], max_new_tokens=5) for _ in range(2)]
    done = eng.run_until_drained()
    assert len(done) == 2 and all(len(r.generated) == 5 for r in done)

    # fit AMM for layer-0 MLP from real activations and check the swapped
    # block stays close on the calibration distribution
    amm_cfg = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True,
                                     quantize_int8=False))
    batch = ts.batch(0)
    emb = np.asarray(params["embed"])[batch["tokens"]].reshape(-1, cfg.d_model)
    l0 = jax.tree.map(lambda a: a[0], params["layers"])
    amm_params = fit_from_dense(
        emb.astype(np.float64), np.asarray(l0["mlp"]["w_gate"]),
        np.asarray(l0["mlp"]["w_up"]), np.asarray(l0["mlp"]["w_down"]),
        amm_cfg)
    xin = jnp.asarray(emb[:64], jnp.float32)[None]
    approx = amm_mlp_apply(amm_params, xin, amm_cfg)[0]
    exact = jax.nn.silu(xin[0] @ l0["mlp"]["w_gate"]) * (
        xin[0] @ l0["mlp"]["w_up"]) @ l0["mlp"]["w_down"]
    rel = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
    assert rel < 1.0  # approximation in range (random-ish acts are hard)
    assert bool(jnp.all(jnp.isfinite(approx)))


def test_pruned_amm_mlp_matches_unpruned_in_model():
    """The model-level AMM-MLP obeys the same losslessness invariant."""
    cfg = get_config("qwen3-14b", reduced=True)
    amm_on = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True, prune=True,
                                     quantize_int8=False))
    amm_off = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, enabled=True, prune=False,
                                     quantize_int8=False))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, cfg.d_model))
    w_gate = rng.normal(size=(cfg.d_model, cfg.d_ff)) / np.sqrt(cfg.d_model)
    w_up = rng.normal(size=(cfg.d_model, cfg.d_ff)) / np.sqrt(cfg.d_model)
    w_down = rng.normal(size=(cfg.d_ff, cfg.d_model)) / np.sqrt(cfg.d_ff)
    p_pruned = fit_from_dense(x, w_gate, w_up, w_down, amm_on)
    p_full = fit_from_dense(x, w_gate, w_up, w_down, amm_off)
    xin = jnp.asarray(x[:32], jnp.float32)[None]
    out_p = amm_mlp_apply(p_pruned, xin, amm_on)
    out_f = amm_mlp_apply(p_full, xin, amm_off)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_f),
                               rtol=1e-4, atol=1e-4)
    # and the pruned tables are half the size (I/d_sub = 4/8)
    assert p_pruned["lut_gate"].shape[-1] * 2 == p_full["lut_gate"].shape[-1]
