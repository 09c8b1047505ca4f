"""End-to-end driver #2: serve a small LM with batched requests through the
continuous-batching engine — first exact, then with the paper's LUT-MU
substituted into every MLP (the serving-side integration).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import TokenStream
from repro.models.amm_mlp import fit_from_dense
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.serving import ServeEngine

cfg = get_config("qwen3-14b", reduced=True)
cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                          vocab_size=256, num_heads=2, num_kv_heads=1,
                          head_dim=32)

print("training a tiny LM on the Markov token stream …")
ts = TokenStream(vocab_size=cfg.vocab_size, batch_size=8, seq_len=64)
tr = Trainer(cfg, TrainerConfig("/tmp/serve_lm_ckpt", ckpt_every=1000,
                                lr=3e-3, warmup_steps=10,
                                compute_dtype=jnp.float32),
             lambda s: ts.batch(s))
out = tr.run(60)
print(f"loss: {out['losses'][0]:.3f} → {out['losses'][-1]:.3f}")
params = tr.state.params

print("\nserving 6 batched requests (exact matmuls) …")
eng = ServeEngine(params, cfg, max_batch=3, max_len=128)
prompts = [list(ts.batch(100 + i)["tokens"][0][:8]) for i in range(6)]
reqs = [eng.submit([int(t) for t in p], max_new_tokens=12) for p in prompts]
t0 = time.time()
done = eng.run_until_drained()
dt = time.time() - t0
n_tok = sum(len(r.generated) for r in done)
print(f"{len(done)} requests, {n_tok} tokens in {dt:.1f}s "
      f"({n_tok / dt:.1f} tok/s on 1 CPU core)")
for r in done[:3]:
    print(f"  req {r.uid}: prompt {r.prompt} → {r.generated}")

print("\nfitting LUT-MU for every MLP from live activations (the paper's "
      "offline training) …")
amm_cfg = dataclasses.replace(
    cfg, amm=dataclasses.replace(cfg.amm, enabled=True, quantize_int8=False))
batch = ts.batch(0)
emb = np.asarray(params["embed"])[batch["tokens"]].reshape(-1, cfg.d_model)
amm_layers = []
for li in range(cfg.num_layers):
    lp = jax.tree.map(lambda a: a[li], params["layers"])
    amm_layers.append(fit_from_dense(
        emb.astype(np.float64), np.asarray(lp["mlp"]["w_gate"]),
        np.asarray(lp["mlp"]["w_up"]), np.asarray(lp["mlp"]["w_down"]),
        amm_cfg, seed=li))
amm_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *amm_layers)
amm_params = dict(params)
amm_params["layers"] = {k: v for k, v in params["layers"].items()
                        if k not in ("mlp",)}
amm_params["layers"]["amm_mlp"] = amm_stacked

print("serving the same requests through the LUT-MU path …")
eng2 = ServeEngine(amm_params, amm_cfg, max_batch=3, max_len=128)
reqs2 = [eng2.submit([int(t) for t in p], max_new_tokens=12) for p in prompts]
done2 = eng2.run_until_drained()
agree = np.mean([
    np.mean([a == b for a, b in zip(r1.generated, r2.generated)])
    for r1, r2 in zip(done, done2)])
print(f"token agreement exact vs LUT-MU serving: {agree:.2f} "
      f"(approximate-matmul drift is the paper's accuracy trade)")
