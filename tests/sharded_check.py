"""Sharded-serving parity driver (run by ``tests/test_serving_sharded.py``).

Executed in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (jax pins the device
count at first init, so the main test process can't fake devices itself).

Checks, in order:

  1. dispatch parity — ``lutmu_matmul_sharded`` vs ``lutmu_matmul`` on a
     2×4 mesh: bit-identical for int8 LUTs (integer partials are exact in
     float32, so the psum + single epilogue reproduce ``contract_onehot``
     arithmetic exactly), allclose for float LUTs (codebook-sum
     reassociation), and the indivisible-codebook fallback;
  2. engine parity — the same requests through a 1-device and a faked
     2×2-mesh ``ServeEngine`` must produce identical token streams, for
     both the dense MLP path and the AMM (int8 LUT) path.

Not a pytest module on purpose (no ``test_`` prefix).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh


def _random_params(b, c, n, depth, *, int8, seed=0):
    from repro.core import maddness as M

    g = 2 ** depth
    rng = np.random.default_rng(seed)
    tree = M.HashTree(
        split_dims=jnp.asarray(rng.integers(0, 4, (c, depth)), jnp.int32),
        thresholds=jnp.asarray(rng.normal(size=(c, g - 1)), jnp.float32))
    if int8:
        lut = jnp.asarray(rng.integers(-128, 128, (c, g, n)), jnp.int8)
        scale = jnp.full((n,), 0.01, jnp.float32)
    else:
        lut = jnp.asarray(rng.normal(size=(c, g, n)), jnp.float32)
        scale = jnp.ones((), jnp.float32)
    offset = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    params = M.MaddnessParams(tree, jnp.zeros((c, g, 0), jnp.float32), lut,
                              scale, offset)
    xs = jnp.asarray(rng.normal(size=(b, c, depth)), jnp.float32)
    return xs, params


def check_dispatch_parity(mesh):
    from repro.kernels.dispatch import BACKENDS, lutmu_matmul, lutmu_matmul_sharded

    # every backend explicitly — off-TPU "auto" always picks ref, which
    # would leave the Pallas backends' shard_map path (interpret mode here)
    # uncovered
    for be in BACKENDS:
        for int8 in (True, False):
            xs, params = _random_params(16, 8, 32, 3, int8=int8)
            ref = lutmu_matmul(xs, params, backend="ref", input_kind="split")
            shd = lutmu_matmul_sharded(xs, params, mesh=mesh, backend=be,
                                       input_kind="split")
            if int8:
                assert bool(jnp.all(ref == shd)), (
                    f"int8 sharded path not bit-identical (backend={be})")
            else:
                assert bool(jnp.allclose(ref, shd, atol=1e-5)), (
                    be, float(jnp.max(jnp.abs(ref - shd))))
    # codebook count indivisible by the tp axis → replicated fallback
    xs, params = _random_params(16, 6, 32, 3, int8=False)
    ref = lutmu_matmul(xs, params, backend="ref", input_kind="split")
    shd = lutmu_matmul_sharded(xs, params, mesh=mesh, input_kind="split")
    assert bool(jnp.allclose(ref, shd, atol=1e-5))
    print("[sharded_check] dispatch parity OK")


def _tiny_cfg(amm):
    from repro.configs import get_config

    cfg = get_config("qwen3-14b", reduced=True)
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              vocab_size=64, num_heads=2, num_kv_heads=1,
                              head_dim=32)
    if amm:
        cfg = dataclasses.replace(
            cfg, amm=dataclasses.replace(cfg.amm, enabled=True))
    return cfg


def check_engine_parity(amm):
    from repro.models import model as MD
    from repro.serving import ServeEngine

    cfg = _tiny_cfg(amm)
    params = MD.init_params(cfg, jax.random.PRNGKey(0), serving=amm)
    prompts = [[1, 2, 3], [7, 5], [9, 9, 9, 2], [4, 4]]

    def run(mesh):
        eng = ServeEngine(params, cfg, max_batch=2, max_len=64, mesh=mesh)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_drained()
        return [r.generated for r in reqs]

    single = run(None)
    sharded = run(make_mesh((2, 2), ("data", "model")))
    assert single == sharded, (amm, single, sharded)
    print(f"[sharded_check] engine parity OK (amm={amm})")


def main():
    n = len(jax.devices())
    assert n >= 8, f"need 8 faked host devices, got {n} (set XLA_FLAGS)"
    check_dispatch_parity(make_mesh((2, 4), ("data", "model")))
    check_engine_parity(amm=False)
    check_engine_parity(amm=True)
    print("[sharded_check] all OK")


if __name__ == "__main__":
    main()
