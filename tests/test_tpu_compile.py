"""The main path's Pallas kernels compile for a TPU v5e at qwen3-14b widths.

Each test AOT-compiles one kernel for a *described* v5e chip (nothing
runs, no chip is needed) and asserts the compiled program holds the
kernel as a ``tpu_custom_call`` under the ``name=`` its ``pallas_call``
gives (the name a device trace shows for it).  Interpret-mode tests
cannot see what Mosaic refuses (lane-axis indexing, unaligned blocks,
vector-held DMA indices); these can.

Shapes are the qwen3-14b LUT-MU sites at ``d_sub=8, depth=4`` with chain
pruning: gate/up read ``C = 5120/8 = 640`` codebooks into ``4·17408/8 =
8704`` pruned columns; down reads ``C = 17408/8 = 2176`` codebooks into
``d_model = 5120``.  Rows are a 256-token prefill chunk and an 8-row
decode batch.  The verify window is ``W = 5`` (``spec_k = 4``) over 8 kv
heads of 128 with 5 query heads each.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune as AT
from repro.kernels.fused_lutmu import fused_lutmu_pallas
from repro.kernels.fused_verify import verify_window_attend_pallas
from repro.kernels.lut_aggregate import lut_aggregate_pallas
from repro.kernels.maddness_encode import encode_onehot_pallas

DEPTH = 4
G = 2**DEPTH
GATE_UP = (640, 8704)    # (C, N)
DOWN = (2176, 5120)
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip cannot read back what it writes to a persistent
    # compilation cache, so keep the cache out of these compiles
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _holds_kernel(text: str, name: str) -> bool:
    """Whether the compiled program holds a Mosaic kernel named ``name``."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*{KERNEL}", text) is not None


@pytest.mark.parametrize("rows", [256, 8])
@pytest.mark.parametrize("site", ["gate_up", "down"])
def test_fused_lutmu_int8_compiles(one_chip, site, rows):
    c, n = GATE_UP if site == "gate_up" else DOWN
    t = AT.heuristic_tiles(rows, c, n, DEPTH, lut_itemsize=1)
    text = _compiled_text(
        lambda x, th, lut, s, o: fused_lutmu_pallas(
            x, th, lut, s, o, depth=DEPTH, block_b=t.block_b,
            block_n=t.block_n, block_c=t.block_c, interpret=False),
        _spec(one_chip, (rows, c, DEPTH), jnp.float32),
        _spec(one_chip, (c, G - 1), jnp.float32),
        _spec(one_chip, (c, G, n), jnp.int8),
        _spec(one_chip, (n,), jnp.float32),
        _spec(one_chip, (n,), jnp.float32))
    assert _holds_kernel(text, "fused_lutmu")


@pytest.mark.parametrize("site", ["gate_up", "down"])
def test_encode_onehot_compiles(one_chip, site):
    c, _ = GATE_UP if site == "gate_up" else DOWN
    text = _compiled_text(
        lambda x, th: encode_onehot_pallas(x, th, depth=DEPTH,
                                           out_dtype=jnp.int8,
                                           interpret=False),
        _spec(one_chip, (256, c, DEPTH), jnp.float32),
        _spec(one_chip, (c, G - 1), jnp.float32))
    assert _holds_kernel(text, "maddness_encode")


def test_lut_aggregate_int8_compiles(one_chip):
    c, n = GATE_UP
    text = _compiled_text(
        lambda oh, lut, s, o: lut_aggregate_pallas(oh, lut, s, o,
                                                   interpret=False),
        _spec(one_chip, (256, c, G), jnp.int8),
        _spec(one_chip, (c, G, n), jnp.int8),
        _spec(one_chip, (n,), jnp.float32),
        _spec(one_chip, (n,), jnp.float32))
    assert _holds_kernel(text, "lut_aggregate")


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
def test_verify_window_compiles(one_chip, kv_dtype):
    b, w, nkv, g, hd = 8, 5, 8, 5, 128
    page, max_pages = 16, 32
    pages = b * max_pages + 1
    t = AT.verify_heuristic_tiles(max_pages * page, w, nkv, g, hd,
                                  jnp.dtype(kv_dtype).itemsize, page)
    assert t is not None
    text = _compiled_text(
        lambda q, k, v, pt, pos, win: verify_window_attend_pallas(
            q, k, v, pt, pos, win, block_s=t.block_s, interpret=False),
        _spec(one_chip, (b, w, nkv, g, hd), jnp.bfloat16),
        _spec(one_chip, (pages, page, nkv, hd), kv_dtype),
        _spec(one_chip, (pages, page, nkv, hd), kv_dtype),
        _spec(one_chip, (b, max_pages), jnp.int32),
        _spec(one_chip, (b,), jnp.int32),
        _spec(one_chip, (), jnp.int32))
    assert _holds_kernel(text, "verify_window")
