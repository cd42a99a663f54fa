"""DeepSeekMoE-16B [moe] — 2 shared + 64 routed top-6, fine-grained  [arXiv:2401.06066]

CONFIG is the published architecture (the model's own config.json,
``source``): 28 layers, layer 0 a dense SiLU-gated MLP of 10944, then
27 MoE layers of 64 routed experts of 1408 (softmax gate, greedy top-6,
the six weights NOT renormalised: ``norm_topk_prob`` false) plus 2
shared experts; MHA 16 x 128, untied vocab 102400, rope 10000, 4096
positions.  The published model has no sliding window, so none is set.
REDUCED is the same family at smoke-test scale (2 layers, d_model<=512,
<=4 experts) for CPU tests.
"""

from repro.models.common import ModelConfig

CONFIG = ModelConfig(
    arch_id='deepseek-moe-16b',
    family='moe',
    n_layers=28,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=0,
    vocab=102400,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    moe_d_ff=1408,
    first_layer_dense_ff=10944,
    act='silu',
    norm_topk_prob=False,
    source='https://huggingface.co/deepseek-ai/deepseek-moe-16b-base/blob/main/config.json',
)

REDUCED = ModelConfig(
    arch_id='deepseek-moe-16b-smoke',
    family='moe',
    n_layers=2,
    d_model=256,
    n_heads=4,
    n_kv_heads=4,
    d_ff=0,
    vocab=512,
    n_experts=4,
    top_k=2,
    n_shared_experts=1,
    moe_d_ff=128,
    first_layer_dense_ff=512,
    act='silu',
    capacity_factor=8.0,
    norm_topk_prob=False,
    dtype='float32',
    source='arXiv:2401.06066',
)
