"""A benchmark configuration, read from ``configs/<name>.json``.

The file holds the model's public ``config.json`` keys as they are run
(top level), the keys changed from the source (``reduced``), the sizes set
here (``assumed``), the LUT-MU settings, the engine settings and the limits
of the correctness comparison.  Everything the harness, the weight maker
and the reference need about a model comes from this one file.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    max_positions: int
    lutmu: bool
    d_sub: int
    depth: int
    prune: bool
    # engine
    max_batch: int
    max_len: int
    page_size: int
    prefill_chunk: int
    kv_pool_tokens: int
    # correctness: (number, limit) pairs a correct run stays within, and
    # how many served tokens the check samples
    limits: tuple
    sample_tokens: int

    @property
    def c_up(self) -> int:
        return self.d_model // self.d_sub

    @property
    def c_down(self) -> int:
        return self.d_ff // self.d_sub

    @property
    def leaves(self) -> int:
        return 2 ** self.depth

    @property
    def package(self) -> int:
        """Columns of the gate/up tables: the down tree's split values
        when pruning is on, else the whole ``d_ff``."""
        return self.depth * self.c_down if self.prune else self.d_ff


def load(name: str, folder: Path = HERE / "configs") -> ModelSpec:
    raw = json.loads((folder / f"{name}.json").read_text())
    if raw.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {raw.get('name')!r}")
    lut = raw["lutmu"]
    eng = raw["engine"]
    ok = raw["correct"]
    return ModelSpec(
        name=name,
        layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"],
        n_heads=raw["num_attention_heads"],
        n_kv_heads=raw["num_key_value_heads"],
        head_dim=raw["head_dim"],
        d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"],
        norm_eps=raw["rms_norm_eps"],
        rope_theta=float(raw["rope_theta"]),
        max_positions=raw["max_position_embeddings"],
        lutmu=lut["enabled"],
        d_sub=lut["d_sub"],
        depth=lut["depth"],
        prune=lut["prune"],
        max_batch=eng["max_batch"],
        max_len=eng["max_len"],
        page_size=eng["page_size"],
        prefill_chunk=eng["prefill_chunk"],
        kv_pool_tokens=eng["kv_pool_tokens"],
        limits=tuple((k, float(v)) for k, v in ok["limits"].items()),
        sample_tokens=ok["sample_tokens"],
    )
