"""Property-based dispatch tests: random (shape, backend, input-kind,
resolution-config) tuples must agree with the ``kernels/ref.py`` oracle —
**exactly** for integer LUT paths (int32 sums are exact in float32),
within per-dtype tolerances for float.

Runs under real hypothesis in CI (``requirements-dev.txt``); without it
the ``@given`` tests skip via ``_hypothesis_stub`` and the fixed
corner-grid test below still pins the same property on the edge shapes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_stub import example, given, settings, st

from repro.core import maddness as M
from repro.kernels import dispatch as D
from repro.kernels import ref

# resolution configs: (lut dtype, epilogue form).  Integer LUTs get a unit
# epilogue so every backend's output is an exact integer-valued float and
# the comparison can be bitwise; the float/"affine" configs exercise the
# per-column dequant epilogue under tolerance.
RESOLUTIONS = ("float32", "float32-affine", "int8", "int8-affine")


def _random_problem(B, Dm, N, C, depth, resolution, seed):
    # D is partitioned into C contiguous subspaces of D//C; split dims
    # index within a subspace (gather_split_values semantics)
    assert Dm % C == 0
    rng = np.random.default_rng(seed)
    g = 2 ** depth
    tree = M.HashTree(
        split_dims=jnp.asarray(rng.integers(0, Dm // C, (C, depth)),
                               jnp.int32),
        thresholds=jnp.asarray(rng.normal(size=(C, g - 1)), jnp.float32))
    if resolution.startswith("int8"):
        lut = jnp.asarray(rng.integers(-128, 128, (C, g, N)), jnp.int8)
    else:
        lut = jnp.asarray(rng.normal(size=(C, g, N)).astype(np.float32))
    if resolution.endswith("affine"):
        scale = jnp.asarray(rng.uniform(0.5, 2.0, (N,)).astype(np.float32))
        offset = jnp.asarray(rng.normal(size=(N,)).astype(np.float32))
    else:
        scale = jnp.ones((), jnp.float32)
        offset = jnp.zeros((), jnp.float32)
    params = M.MaddnessParams(tree, jnp.zeros((C, g, 0), jnp.float32), lut,
                              scale, offset)
    x = jnp.asarray(rng.normal(size=(B, Dm)).astype(np.float32))
    return x, params


def _check_backends_agree(B, Dm, N, C, depth, resolution, input_kind, seed):
    """The property: every backend × input-kind matches the oracle."""
    x, p = _random_problem(B, Dm, N, C, depth, resolution, seed)
    xs = M.gather_split_values(x, p.tree)
    want = np.asarray(ref.fused_lutmu_ref(xs, p.tree.thresholds, p.lut,
                                          p.lut_scale, p.lut_offset))
    inp = {"full": x, "split": xs,
           "package": jnp.transpose(xs, (0, 2, 1)).reshape(B, -1)}[input_kind]
    for backend in D.BACKENDS:
        got = np.asarray(D.lutmu_matmul(inp, p, backend=backend,
                                        input_kind=input_kind,
                                        interpret=True))
        msg = (f"backend={backend} kind={input_kind} res={resolution} "
               f"shape=(B={B},D={Dm},N={N},C={C},I={depth}) seed={seed}")
        if resolution == "int8":
            # exact int path: int32 accumulation, unit epilogue → bitwise
            np.testing.assert_array_equal(got, want, err_msg=msg)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=msg)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_random_backend_parity(data):
    B = data.draw(st.integers(1, 40), label="B")
    C = data.draw(st.sampled_from([1, 2, 4, 6, 8]), label="C")
    depth = data.draw(st.integers(1, 4), label="depth")
    N = data.draw(st.sampled_from([1, 8, 16, 24, 129, 256]), label="N")
    Dm = C * data.draw(st.sampled_from([2, 4, 8]), label="d_sub")
    resolution = data.draw(st.sampled_from(RESOLUTIONS), label="resolution")
    kind = data.draw(st.sampled_from(D.INPUT_KINDS), label="input_kind")
    seed = data.draw(st.integers(0, 2**20), label="seed")
    _check_backends_agree(B, Dm, N, C, depth, resolution, kind, seed)


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_random_auto_matches_forced_ref(data):
    """``backend="auto"`` may pick any backend — its result must still
    match the explicitly forced ref backend within float tolerance."""
    B = data.draw(st.integers(1, 64))
    C = data.draw(st.sampled_from([2, 4, 8]))
    depth = data.draw(st.integers(2, 4))
    N = data.draw(st.sampled_from([16, 48, 129]))
    seed = data.draw(st.integers(0, 2**20))
    x, p = _random_problem(B, 8 * C, N, C, depth, "float32-affine", seed)
    want = D.lutmu_matmul(x, p, backend="ref")
    got = D.lutmu_matmul(x, p, backend="auto")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# The same property on a fixed corner grid — runs even without hypothesis,
# and pins the edge shapes (B=1, N=1, depth=1, single codebook) that a
# bad tile clamp or trailing-tile mask would break first.
CORNERS = [
    # (B, D, N, C, depth)
    (1, 8, 1, 1, 1),
    (1, 32, 8, 2, 1),
    (7, 32, 24, 4, 3),
    (33, 64, 129, 8, 4),
    (40, 48, 256, 6, 2),
]


@pytest.mark.parametrize("shape", CORNERS)
@pytest.mark.parametrize("resolution", RESOLUTIONS)
def test_corner_grid_backend_parity(shape, resolution):
    B, Dm, N, C, depth = shape
    for kind in D.INPUT_KINDS:
        _check_backends_agree(B, Dm, N, C, depth, resolution, kind, seed=3)


# ---------------------------------------------------------------------------
# The portable fused-verify lowering over a random shape grid.  The
# property is *bitwise* for every dtype: each window position of the
# compiled ``verify_window_attend`` equals the compiled one-token
# ``decode_attend`` read at that position, as the engine runs both.
# ---------------------------------------------------------------------------


def _check_verify_window_bitwise(b, w, s, nkv, g, hd, int8, windowed, seed):
    from repro.kernels import fused_verify as FV

    rng = np.random.default_rng(seed)
    if int8:
        kv = jnp.asarray(rng.integers(-127, 128, (b, s, nkv, hd)), jnp.int8)
        vv = jnp.asarray(rng.integers(-127, 128, (b, s, nkv, hd)), jnp.int8)
    else:
        kv = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)
        vv = jnp.asarray(rng.normal(size=(b, s, nkv, hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, w, nkv, g, hd)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, max(1, s - w), b), jnp.int32)
    win = jnp.asarray(rng.integers(1, s + 1), jnp.int32) if windowed else None
    got = jax.jit(FV.verify_window_attend)(q, kv, vv, pos, win)
    oracle = jax.jit(FV.decode_attend)
    msg = (f"b={b} w={w} s={s} nkv={nkv} g={g} hd={hd} int8={int8} "
           f"windowed={windowed} seed={seed}")
    for j in range(w):
        want = oracle(q[:, j:j + 1], kv, vv, pos + j, win)
        np.testing.assert_array_equal(np.asarray(got[:, j]),
                                      np.asarray(want[:, 0]),
                                      err_msg=f"{msg} j={j}")


@settings(max_examples=25, deadline=None)
@given(b=st.integers(1, 4), w=st.integers(1, 6),
       s=st.sampled_from([8, 16, 24, 64]), nkv=st.sampled_from([1, 2]),
       g=st.sampled_from([1, 2, 4]), hd=st.sampled_from([4, 8, 32]),
       int8=st.booleans(), windowed=st.booleans(),
       seed=st.integers(0, 2**20))
# one int8 step apart at j=5 when the oracle ran op by op, outside jit
@example(b=4, w=6, s=16, nkv=2, g=4, hd=8, int8=True, windowed=True,
         seed=738198)
# the int8 window as a lax.scan did not compile on XLA:CPU at this shape
@example(b=1, w=5, s=8, nkv=1, g=2, hd=4, int8=True, windowed=True,
         seed=238267)
def test_random_verify_window_matches_oracle(b, w, s, nkv, g, hd, int8,
                                             windowed, seed):
    _check_verify_window_bitwise(b, w, s, nkv, g, hd, int8, windowed, seed)


# fixed corners (runs without hypothesis): W=1 degenerates to one decode
# step, W=S fills the whole view, single-head, GQA fan-out
VERIFY_CORNERS = [
    # (b, w, s, nkv, g, hd)
    (1, 1, 8, 1, 1, 4),
    (2, 4, 16, 1, 4, 8),
    (3, 6, 24, 2, 2, 32),
    (1, 8, 8, 2, 1, 8),
]


@pytest.mark.parametrize("shape", VERIFY_CORNERS)
@pytest.mark.parametrize("int8", [False, True])
def test_verify_window_corner_grid(shape, int8):
    b, w, s, nkv, g, hd = shape
    for windowed in (False, True):
        _check_verify_window_bitwise(b, w, s, nkv, g, hd, int8, windowed,
                                     seed=5)
