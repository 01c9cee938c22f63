"""Architecture configuration schema and the shape cell of a run.

Each assigned architecture gets one ``src/repro/configs/<id>.py`` exporting
``CONFIG`` (the exact published configuration) and ``SMOKE`` (a reduced
same-family config for CPU smoke tests).  ``repro.configs.get(name)``
resolves either.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

__all__ = ["ArchConfig", "ShapeCell", "round_up"]


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | mla | moe | vlm | ssm | hybrid | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    tie_embeddings: bool = False

    # ffn / moe
    ffn_kind: str = "swiglu"  # swiglu | gelu | moe
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_dense_residual: bool = False
    # grouped dispatch (GShard-style): 0/1 = one global group; set to the
    # data-parallel degree so routing/capacity stay shard-local and the
    # dispatch scatter never crosses the data axis (§Perf lever)
    moe_groups: int = 0
    # "auto" = dense/grouped capacity dispatch; "ep" = expert-parallel ragged
    # all-to-all dispatch over the model axis (repro.models.ffn docstring) —
    # falls back to auto (with a warning) when the recipe cannot host it
    moe_dispatch: str = "auto"

    # MLA (minicpm3)
    mla_q_rank: int = 768
    mla_kv_rank: int = 256
    mla_d_nope: int = 64
    mla_d_rope: int = 32
    mla_d_v: int = 64

    # SSM (rwkv6 / mamba2)
    ssm_state: int = 64
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 64

    # VLM (llama-3.2-vision)
    enc_dim: int = 4096
    enc_len: int = 1024
    cross_every: int = 5  # every 5th layer is cross-attention

    # hybrid (zamba2)
    shared_every: int = 6  # every 6th block is the shared attention block
    shared_lora_rank: int = 8
    shared_window: int = 4096  # long-context window for the shared attn (500k cell)

    # execution
    attn_impl: str | None = None  # None -> backend default (pallas on TPU)
    attn_mixed: bool | None = None  # bf16 attention streams; None -> backend auto
    attn_block: int = 512
    param_dtype: Any = jnp.float32
    act_dtype: Any = jnp.bfloat16
    remat: str = "block"  # none | block
    input_kind: str = "tokens"  # tokens | embeds | tokens+image
    notes: str = ""

    # ------------------------------------------------------------------
    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up for clean sharding (Megatron-style padding)."""
        return round_up(self.vocab, 256)

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run the long_500k cell? (SSM / hybrid only)"""
        return self.family in ("ssm", "hybrid")

    def supported_shapes(self) -> list[str]:
        out = ["train_4k", "prefill_32k", "decode_32k"]
        if self.sub_quadratic:
            out.append("long_500k")
        return out

    # parameter count estimate (for MODEL_FLOPS = 6*N*D)
    def param_count(self, *, active_only: bool = False) -> int:
        from repro.models import lm

        return lm.count_params(self, active_only=active_only)
