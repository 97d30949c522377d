"""Architecture configuration dataclasses."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Mapping, Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden size
    num_shared: int = 0           # always-active shared experts
    first_dense_layers: int = 0   # leading layers use a dense FFN
    dense_d_ff: int = 0           # hidden size of those dense layers
    capacity_factor: float = 1.25
    serve_capacity_factor: float = 3.0  # decode/prefill headroom (no-drop margin)
    aux_loss_weight: float = 1e-3
    # capacity dispatch cost scales as group_tokens^2 * K * cf * D — small
    # groups keep the one-hot dispatch einsums a fraction of expert FLOPs
    # (dispatch/expert ~ group_tokens * cf / (3 * d_expert)).
    group_tokens: int = 1024
    map_chunk_groups: int = 4096  # lax.map chunking escape hatch: only
                                  # engages for >4096 groups (dispatch temps
                                  # are mesh-sharded, so vmap is the default;
                                  # each map step re-gathers expert weights)
    dropless: bool = False        # True: sort + ragged_dot (exact; used by
                                  # smoke/tests — the XLA fallback lowers to
                                  # dense per-expert dots, so big shapes use
                                  # the capacity path)
    # this chip's share under expert parallelism: routed experts
    # [expert_offset, expert_offset + experts_held) live here (0: all).  The
    # router keeps all num_experts outputs; assignments to experts held
    # elsewhere add nothing here.
    experts_held: int = 0
    expert_offset: int = 0
    norm_topk: bool = True        # renormalise the top-k gates to sum to 1
    routed_scale: float = 1.0     # routed output multiplier (DeepSeek's
                                  # routed_scaling_factor)

    def __post_init__(self):
        if not 0 <= self.expert_offset < self.expert_offset + self.held \
                <= self.num_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) outside the "
                f"{self.num_experts} the router covers")

    @property
    def held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    q_lora: int = 1536            # 0: no query compression (a direct wq)
    kv_lora: int = 512
    qk_nope: int = 128
    qk_rope: int = 64
    v_head: int = 128


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 configures it:
    frequencies blended between interpolated and extrapolated over the
    correction range that ``beta_fast``/``beta_slow`` give, cos and sin
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64
    ngroups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.headdim


@dataclass(frozen=True)
class RWKVConfig:
    head_dim: int = 64
    decay_lora: int = 64
    mix_lora: int = 32


@dataclass(frozen=True)
class EncDecConfig:
    n_enc_layers: int = 4
    n_audio_ctx: int = 1500  # encoder positions (conv frontend stubbed)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: int = 0  # 0 => d_model // n_heads
    # block pattern: per-layer type; default all "attn".
    #   "attn"        standard (GQA/MQA) attention block
    #   "mla"         multi-head latent attention block (DeepSeek-V2)
    #   "mamba2"      Mamba2 SSD block
    #   "rwkv6"       RWKV6 block (token mix + channel mix)
    #   "shared_attn" shared-parameter attention block (Zamba2)
    block_pattern: Tuple[str, ...] = ()
    mlp_act: str = "silu"           # silu => SwiGLU, gelu => GeGLU, gelu_mlp => plain
    qkv_bias: bool = False
    parallel_block: bool = False     # attn + mlp in parallel (Command-R)
    tie_embeddings: bool = False
    scale_embed: bool = False        # multiply embeddings by sqrt(d) (Gemma)
    norm: str = "rmsnorm"            # or "layernorm"
    norm_eps: float = 1e-6
    norm_unit_offset: bool = False   # RMSNorm computes (1 + w) * x_hat (Gemma)
    rope_theta: float = 10000.0
    rope_type: str = "standard"      # "standard" | "mrope" | "none"
    rope_scaling: Optional[YarnScaling] = None
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    logit_softcap: float = 0.0
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    enc_dec: Optional[EncDecConfig] = None
    visual_stub: bool = False        # qwen2-vl patch-embedding merge stub
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    vocab_round: int = 256           # pad vocab for shardability
    loss_chunk: int = 1024           # sequence-chunked softmax-xent
    remat: bool = True
    # "nothing": full recompute (min memory); "dots": keep matmul
    # outputs (no dot recompute in bwd — higher useful-FLOP ratio
    # when HBM allows)
    remat_policy: str = "nothing"
    # kernels/ops impl selectors: "auto" runs the Pallas attention kernel
    # on a TPU and the XLA reference elsewhere; the scan kernels stay on
    # "ref" until they compile for the chip at published widths
    attn_impl: str = "auto"
    scan_impl: str = "ref"
    # "fsdp": model axis = extra data/param shards (best for small-to-mid
    # models at large batch); "tp": Megatron activation sharding on the
    # model axis (needed when per-layer weights dwarf activations, e.g.
    # DeepSeek-V2's 160-expert layers where EP is mandatory).  Nothing
    # reads it yet: the activation constraints in models/common.py are
    # fixed at "tp", and launch/sharding.py takes its own ``profile``.
    sharding_profile: str = "fsdp"

    def __post_init__(self):
        # nested groups may come as mappings (a JSON configuration)
        for name, cls in _NESTED.items():
            v = getattr(self, name)
            if isinstance(v, Mapping):
                object.__setattr__(self, name, _from_mapping(cls, v))
        for name in ("block_pattern", "mrope_sections"):
            v = getattr(self, name)
            if not isinstance(v, tuple):
                object.__setattr__(self, name, tuple(v))

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round
        return (self.vocab_size + r - 1) // r * r

    @property
    def blocks(self) -> Tuple[str, ...]:
        if self.block_pattern:
            if len(self.block_pattern) != self.n_layers:
                raise ValueError("block_pattern length must equal n_layers")
            return self.block_pattern
        return ("attn",) * self.n_layers

    def param_jdtype(self):
        import jax.numpy as jnp

        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.param_dtype]

    def compute_jdtype(self):
        import jax.numpy as jnp

        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.compute_dtype]


_NESTED = {"moe": MoEConfig, "mla": MLAConfig, "mamba": MambaConfig,
           "rwkv": RWKVConfig, "enc_dec": EncDecConfig,
           "rope_scaling": YarnScaling}


def _from_mapping(cls, m: Mapping[str, Any]):
    kw = dict(m)
    if cls is YarnScaling and kw.pop("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling {m!r}: only yarn is modelled")
    return cls(**kw)
