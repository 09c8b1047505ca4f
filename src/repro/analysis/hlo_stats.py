"""HLO text analysis: per-collective byte totals for the roofline's third
term (cost_analysis does not expose collective traffic).

We parse the *optimized* (post-SPMD) HLO of the compiled per-device program
and sum the **result-shape bytes** of every collective op.  For all-reduce
the result equals the operand; for all-gather the result is the gathered
tensor (a ring moves (n-1)/n of that per device — we take the full size as a
slightly conservative bound); reduce-scatter uses its operand (= result × n,
so we take the larger operand bytes); all-to-all and collective-permute move
their full result.
"""
from __future__ import annotations

import re
from typing import Dict

def cost_analysis_dict(compiled) -> Dict:
    """``Compiled.cost_analysis()`` as a plain dict (empty when XLA gives
    no analysis)."""
    return dict(compiled.cost_analysis() or {})


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %ag = bf16[8,4096,5376]{2,1,0} all-gather(%x), ...
#        %st = (bf16[8],bf16[128]) all-gather-start(%x), ...
_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([a-z0-9]+)\[([0-9,]*)\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

_TYPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, dict]:
    """Sum result bytes per collective kind.  Returns
    {kind: {"bytes": int, "count": int}, ..., "total_bytes": int}."""
    out: Dict[str, dict] = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        tuple_types, dtype, dims, kind, suffix = m.groups()
        if suffix == "-done":  # async pair: already counted at -start
            continue
        if tuple_types is not None:
            # async-start tuples carry (operand, result, …): take the largest
            b = max((_shape_bytes(t.group(1), t.group(2))
                     for t in _TYPE_RE.finditer(tuple_types)), default=0)
        else:
            b = _shape_bytes(dtype, dims)
        out[kind]["bytes"] += b
        out[kind]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out
