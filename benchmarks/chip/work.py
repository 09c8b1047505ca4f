"""Operations and bytes the algorithm needs, counted from shapes.

These are the yardstick of the roofline and utilisation metrics, so they
count what the model asks for and never what an implementation happens to
do: no one-hot products, no re-encodes per output tile, no padding rows.
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip.spec import ModelSpec

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def lutmu_call(b: int, c: int, g: int, n: int, depth: int) -> tuple:
    """(ops, bytes) of one LUT-MU product of ``b`` rows: encode compares
    b*c*(g-1), aggregate adds b*c*n; bytes of the int8 table c*g*n, the
    float32 split values b*c*depth, thresholds c*(g-1), scale and offset
    2n and the float32 output b*n."""
    ops = b * c * (g - 1) + b * c * n
    nbytes = c * g * n + 4 * (b * c * depth + c * (g - 1) + 2 * n + b * n)
    return ops, nbytes


def lutmu_layer_sites(spec: ModelSpec, b: int) -> list:
    """The (ops, bytes) of the gate, up and down products of one layer."""
    g = spec.leaves
    up = lutmu_call(b, spec.c_up, g, spec.package, spec.depth)
    down = lutmu_call(b, spec.c_down, g, spec.d_model, spec.depth)
    return [up, up, down]


def roofline_s(ops: float, nbytes: float, pk: dict) -> float:
    """Least time the chip could take: ops at the int8 peak or bytes at
    HBM bandwidth, whichever is longer."""
    return max(ops / pk["int8_op_s"], nbytes / pk["hbm_byte_s"])


def token_flops(spec: ModelSpec, context: int, head: bool) -> float:
    """Model FLOPs of one token at position ``context - 1`` (it attends to
    ``context`` positions): every projection as a dense product (a LUT-MU
    MLP counts as the dense product it stands for), attention scores and
    values over the real context, and the LM head if its logits are
    computed."""
    d, hd, ff = spec.d_model, spec.head_dim, spec.d_ff
    nq, nkv = spec.n_heads, spec.n_kv_heads
    proj = d * (nq + 2 * nkv) * hd + nq * hd * d + 3 * d * ff
    attn = 2 * nq * hd * context
    per_layer = 2 * proj + 2 * attn
    return spec.layers * per_layer + (2 * d * spec.vocab if head else 0)
