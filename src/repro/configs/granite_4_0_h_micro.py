"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro]: 40 layers in a
period of ten, Mamba-2 everywhere but layers 5, 15, 25 and 35, which are
NoPE GQA attention; every mixer is followed by a SwiGLU MLP. Granite's
embedding, attention, residual and logits multipliers apply. HCCS replaces
softmax in the four attention layers."""
from repro.configs.base import ModelConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid", num_layers=40, d_model=2048,
    num_heads=32, num_kv_heads=8, head_dim=64, d_ff=8192, vocab_size=100352,
    activation="swiglu", norm="rmsnorm", norm_eps=1e-5, rope="none",
    layer_types=PERIOD * 4, ssm_state=128, ssm_expand=2, ssm_head_dim=64,
    ssm_groups=1, ssm_chunk=256, ssm_conv=4, embedding_multiplier=12.0,
    attention_multiplier=0.015625, residual_multiplier=0.22,
    logits_scaling=8.0, attention_prob="hccs", dtype="bfloat16",
)
