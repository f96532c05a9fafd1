"""Architecture configuration — every assigned arch is an ``ArchConfig``."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense|moe|hybrid|ssm|vlm|audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # block structure: per-layer kind cycles through this pattern
    block_pattern: tuple[str, ...] = ("attn",)   # attn|local|rec|ssm|mamba2|dec
    ffn_kind: str = "glu"             # glu|mlp|moe|none
    activation: str = "silu"
    norm: str = "rms"                 # rms|layer
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True             # False: no positional encoding (NoPE)
    norm_eps: float = 1e-6            # RMSNorm epsilon
    # residual-stream multipliers (Granite); the defaults are the plain
    # transformer: embeddings x sqrt(d_model), scores x 1/sqrt(head_dim),
    # branches added as they are, logits as they are
    embedding_multiplier: float = 0.0   # 0: sqrt(d_model)
    attention_multiplier: float = 0.0   # 0: 1/sqrt(head_dim)
    residual_multiplier: float = 1.0    # each residual branch is scaled by it
    logits_scaling: float = 1.0         # logits are divided by it
    window: int = 0                   # sliding-window size for "local" blocks
    # MoE
    num_experts: int = 0
    top_k: int = 0
    moe_shared_expert: bool = False
    moe_capacity: float = 1.25
    moe_impl: str = "einsum"          # einsum | scatter | ragged (see moe.py)
    # recurrent dims
    rglru_gate_blocks: int = 0        # 0 = dense gates; >0 = block-diagonal
    d_rnn: int = 0                    # RG-LRU width
    d_inner: int = 0                  # Mamba inner width
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    ssm_heads: int = 0                # Mamba-2 heads (head dim d_inner/heads)
    ssm_groups: int = 1               # Mamba-2 B/C groups shared by heads
    # encoder-decoder
    enc_layers: int = 0               # >0 => encoder-decoder (dec uses num_layers)
    # modality frontend stub (assignment: precomputed frame/patch embeddings)
    modality_tokens: int = 0
    modality_dim: int = 0
    tie_embeddings: bool = True
    # execution knobs (tuned per shape by the launcher)
    scan_chunk: int = 512             # recurrence chunk
    attn_block_kv: int = 512          # flash KV block
    remat: bool = True
    attn_f32: bool = True             # False: bf16 score/probability path
                                      # (fp32 m/l accumulators kept)
    unroll_scans: bool = False        # roofline mode: no while loops, so
                                      # compiled.cost_analysis() counts every
                                      # iteration (XLA counts loop bodies once)
    # kernel-variant switches ("xla" reference path | "pallas" fused kernel).
    # Owned by serve/placement.ExecutionPolicy at serving time — the oracle
    # resolves them per cluster before warmup; they never change shapes.
    attn_impl: str = "xla"            # flash prefill / paged decode kernels
    rglru_impl: str = "xla"           # pavlov_rglru linear-scan kernel
    ssm_impl: str = "xla"             # pavlov_ssm selective-scan kernel
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(self.block_pattern[i % len(self.block_pattern)]
                     for i in range(self.num_layers))

    @property
    def vocab_padded(self) -> int:
        """Embedding rows padded to a multiple of 16 so the Jacquard
        vocab-sharded strategy divides any production mesh axis."""
        return -(-self.vocab_size // 16) * 16

    @property
    def sub_quadratic(self) -> bool:
        """True when no block needs a full-length dense KV cache — the
        assignment's criterion for running long_500k."""
        return all(k in ("rec", "ssm", "mamba2", "local")
                   for k in set(self.layer_kinds))

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # parameter count (for roofline MODEL_FLOPS = 6*N*D)
    def param_count(self, active_only: bool = False) -> int:
        d, h = self.d_model, self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        n = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        per_kind = {}
        per_kind["attn"] = per_kind["local"] = d * h + 2 * d * kv + h * d
        per_kind["dec"] = 2 * per_kind["attn"]
        per_kind["rec"] = (2 * self.d_rnn * self.d_rnn
                           + 2 * self.d_model * self.d_rnn
                           + self.d_rnn * self.d_model + 5 * self.d_rnn)
        dtr = self.dt_rank or max(1, d // 16)
        per_kind["ssm"] = (2 * d * self.d_inner
                           + self.d_inner * (dtr + 2 * self.d_state)
                           + dtr * self.d_inner + self.d_inner * d
                           + (self.d_conv + self.d_state + 2) * self.d_inner)
        conv_dim = self.d_inner + 2 * self.ssm_groups * self.d_state
        per_kind["mamba2"] = (d * (conv_dim + self.d_inner + self.ssm_heads)
                              + (self.d_conv + 1) * conv_dim
                              + 3 * self.ssm_heads + self.d_inner
                              + self.d_inner * d)
        if self.ffn_kind == "glu":
            ffn = 3 * d * self.d_ff
        elif self.ffn_kind == "mlp":
            ffn = 2 * d * self.d_ff
        elif self.ffn_kind == "moe":
            e = self.top_k if active_only else self.num_experts
            ffn = e * 3 * d * self.d_ff + d * self.num_experts
            if self.moe_shared_expert:
                ffn += 3 * d * self.d_ff
        else:
            ffn = 0
        for k in self.layer_kinds:
            n += per_kind[k] + (ffn if k != "ssm" else 0)
        if self.is_encdec:
            n += self.enc_layers * (per_kind["attn"] + ffn)
        if self.modality_tokens:
            n += self.modality_dim * d + d * d   # 2-layer projector
        return n
