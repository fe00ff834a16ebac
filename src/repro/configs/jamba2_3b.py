"""AI21-Jamba2-3B [hf: ai21labs/AI21-Jamba2-3B config.json]: 28 layers, 26
Mamba-1 mixers and attention in 2 (period 14, offset 7; the config gives
the period and offset, the order is Jamba's ``i % period == offset``), 20
query heads over 1 KV head of width 128, no positional encoding, a dense
SwiGLU MLP on every layer (``num_experts`` 1), Mamba d_state 16, expand 2,
dt_rank 160, d_conv 4, RMSNorms on dt/B/C, RMSNorm eps 1e-6, tied
embeddings. 3,029,337,472 parameters."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="jamba2_3b",
    family="hybrid",
    num_layers=28,
    d_model=2560,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    attn_period=14,
    attn_offset=7,
    ssm_state_dim=16,
    ssm_conv_width=4,
    ssm_expand=2,
    ssm_dt_rank=160,
    ssm_inner_norms=True,
    position_encoding="none",
    norm_eps=1e-6,
    tie_embeddings=True,
)
