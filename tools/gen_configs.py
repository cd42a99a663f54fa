"""One-shot generator for src/repro/configs/<arch>.py files."""
import os

HEADER = '''"""{title}  {cite}

Auto-structured config: CONFIG is the exact assigned architecture;
REDUCED is the same family at smoke-test scale (2 layers, d_model<=512,
<=4 experts) for CPU tests.
"""

from repro.models.common import ModelConfig

'''

ARCHS = {
    "phi4_mini_3_8b": dict(
        title="Phi-4-mini 3.8B [dense]", cite="[arXiv:2412.08905]",
        CONFIG=dict(arch_id="phi4-mini-3.8b", family="dense", n_layers=32,
                    d_model=3072, n_heads=24, n_kv_heads=8, d_ff=8192,
                    vocab=200064, act="silu", sliding_window=8192,
                    source="arXiv:2412.08905"),
        REDUCED=dict(arch_id="phi4-mini-3.8b-smoke", family="dense",
                     n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                     d_ff=512, vocab=512, act="silu", sliding_window=64,
                     dtype="float32", source="arXiv:2412.08905")),
    "mamba2_780m": dict(
        title="Mamba2-780m [ssm] — SSD (state-space duality)",
        cite="[arXiv:2405.21060]",
        CONFIG=dict(arch_id="mamba2-780m", family="ssm", n_layers=48,
                    d_model=1536, n_heads=0, n_kv_heads=0, d_ff=0,
                    vocab=50280, ssm_state=128, ssm_head_dim=64,
                    ssm_expand=2, ssm_conv=4, ssm_groups=1,
                    tie_embeddings=True, source="arXiv:2405.21060"),
        REDUCED=dict(arch_id="mamba2-780m-smoke", family="ssm",
                     n_layers=2, d_model=256, n_heads=0, n_kv_heads=0,
                     d_ff=0, vocab=512, ssm_state=16, ssm_head_dim=32,
                     ssm_expand=2, ssm_conv=4, ssm_groups=1,
                     tie_embeddings=True, dtype="float32",
                     source="arXiv:2405.21060")),
    "qwen3_32b": dict(
        title="Qwen3-32B [dense] — qk_norm, GQA", cite="[hf:Qwen/Qwen3-8B]",
        CONFIG=dict(arch_id="qwen3-32b", family="dense", n_layers=64,
                    d_model=5120, n_heads=64, n_kv_heads=8, d_ff=25600,
                    vocab=151936, head_dim=128, qk_norm=True, act="silu",
                    rope_base=1000000.0, sliding_window=8192,
                    source="hf:Qwen/Qwen3-8B"),
        REDUCED=dict(arch_id="qwen3-32b-smoke", family="dense", n_layers=2,
                     d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
                     vocab=512, head_dim=64, qk_norm=True, act="silu",
                     dtype="float32", source="hf:Qwen/Qwen3-8B")),
    "phi3_mini_3_8b": dict(
        title="Phi-3-mini 3.8B [dense]", cite="[arXiv:2404.14219]",
        CONFIG=dict(arch_id="phi3-mini-3.8b", family="dense", n_layers=32,
                    d_model=3072, n_heads=32, n_kv_heads=32, d_ff=8192,
                    vocab=32064, act="silu", sliding_window=8192,
                    source="arXiv:2404.14219"),
        REDUCED=dict(arch_id="phi3-mini-3.8b-smoke", family="dense",
                     n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
                     d_ff=512, vocab=512, act="silu", dtype="float32",
                     source="arXiv:2404.14219")),
    "deepseek_moe_16b": dict(
        title="DeepSeekMoE-16B [moe] — 2 shared + 64 routed top-6, "
              "fine-grained", cite="[arXiv:2401.06066]",
        CONFIG=dict(arch_id="deepseek-moe-16b", family="moe", n_layers=28,
                    d_model=2048, n_heads=16, n_kv_heads=16, d_ff=0,
                    vocab=102400, n_experts=64, top_k=6,
                    n_shared_experts=2, moe_d_ff=1408,
                    first_layer_dense_ff=10944, act="silu",
                    norm_topk_prob=False,
                    source="https://huggingface.co/deepseek-ai/"
                           "deepseek-moe-16b-base/blob/main/config.json"),
        REDUCED=dict(arch_id="deepseek-moe-16b-smoke", family="moe",
                     n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
                     d_ff=0, vocab=512, n_experts=4, top_k=2,
                     n_shared_experts=1, moe_d_ff=128,
                     first_layer_dense_ff=512, act="silu",
                     capacity_factor=8.0, norm_topk_prob=False,
                     dtype="float32", source="arXiv:2401.06066")),
    "yi_6b": dict(
        title="Yi-6B [dense] — llama-arch GQA", cite="[arXiv:2403.04652]",
        CONFIG=dict(arch_id="yi-6b", family="dense", n_layers=32,
                    d_model=4096, n_heads=32, n_kv_heads=4, d_ff=11008,
                    vocab=64000, act="silu", rope_base=5000000.0,
                    sliding_window=8192, source="arXiv:2403.04652"),
        REDUCED=dict(arch_id="yi-6b-smoke", family="dense", n_layers=2,
                     d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
                     vocab=512, act="silu", dtype="float32",
                     source="arXiv:2403.04652")),
    "qwen3_moe_30b_a3b": dict(
        title="Qwen3-30B-A3B [moe] — 128 experts top-8",
        cite="[hf:Qwen/Qwen3-30B-A3B]",
        CONFIG=dict(arch_id="qwen3-moe-30b-a3b", family="moe", n_layers=48,
                    d_model=2048, n_heads=32, n_kv_heads=4, d_ff=0,
                    vocab=151936, head_dim=128, qk_norm=True,
                    n_experts=128, top_k=8, moe_d_ff=768, act="silu",
                    norm_topk_prob=True, rope_base=1000000.0,
                    sliding_window=8192,
                    source="hf:Qwen/Qwen3-30B-A3B"),
        REDUCED=dict(arch_id="qwen3-moe-30b-a3b-smoke", family="moe",
                     n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
                     d_ff=0, vocab=512, head_dim=64, qk_norm=True,
                     n_experts=4, top_k=2, moe_d_ff=128, act="silu",
                     capacity_factor=8.0, dtype="float32", source="hf:Qwen/Qwen3-30B-A3B")),
    "paligemma_3b": dict(
        title="PaliGemma-3B [vlm] — SigLIP + Gemma (ViT stubbed)",
        cite="[arXiv:2407.07726]",
        CONFIG=dict(arch_id="paligemma-3b", family="vlm", n_layers=18,
                    d_model=2048, n_heads=8, n_kv_heads=1, d_ff=16384,
                    vocab=257216, head_dim=256, act="geglu",
                    tie_embeddings=True, n_vision_tokens=256,
                    d_vision=1152, prefix_lm=True, sliding_window=8192,
                    source="arXiv:2407.07726"),
        REDUCED=dict(arch_id="paligemma-3b-smoke", family="vlm",
                     n_layers=2, d_model=256, n_heads=4, n_kv_heads=1,
                     d_ff=512, vocab=512, head_dim=64, act="geglu",
                     tie_embeddings=True, n_vision_tokens=16,
                     d_vision=64, prefix_lm=True, dtype="float32",
                     source="arXiv:2407.07726")),
    "whisper_large_v3": dict(
        title="Whisper-large-v3 [audio] — enc-dec; conv frontend stubbed",
        cite="[arXiv:2212.04356]",
        CONFIG=dict(arch_id="whisper-large-v3", family="audio",
                    n_layers=32, d_model=1280, n_heads=20, n_kv_heads=20,
                    d_ff=5120, vocab=51866, act="gelu", rope_base=0.0,
                    n_encoder_layers=32, n_audio_ctx=1500,
                    tie_embeddings=True, sliding_window=8192,
                    source="arXiv:2212.04356"),
        REDUCED=dict(arch_id="whisper-large-v3-smoke", family="audio",
                     n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
                     d_ff=256, vocab=512, act="gelu", rope_base=0.0,
                     n_encoder_layers=2, n_audio_ctx=32,
                     tie_embeddings=True, dtype="float32",
                     source="arXiv:2212.04356")),
    "zamba2_1_2b": dict(
        title="Zamba2-1.2B [hybrid] — Mamba2 + shared attn blocks",
        cite="[arXiv:2411.15242]",
        CONFIG=dict(arch_id="zamba2-1.2b", family="hybrid", n_layers=38,
                    d_model=2048, n_heads=32, n_kv_heads=32, d_ff=8192,
                    vocab=32000, ssm_state=64, ssm_head_dim=64,
                    ssm_expand=2, ssm_conv=4, ssm_groups=1,
                    shared_attn_every=6, act="gelu",
                    source="arXiv:2411.15242"),
        REDUCED=dict(arch_id="zamba2-1.2b-smoke", family="hybrid",
                     n_layers=4, d_model=256, n_heads=4, n_kv_heads=4,
                     d_ff=512, vocab=512, ssm_state=16, ssm_head_dim=32,
                     ssm_expand=2, ssm_conv=4, ssm_groups=1,
                     shared_attn_every=2, act="gelu", dtype="float32",
                     source="arXiv:2411.15242")),
}


def fmt(d):
    items = ",\n    ".join(f"{k}={v!r}" for k, v in d.items())
    return f"ModelConfig(\n    {items},\n)"


def main():
    base = os.path.join(os.path.dirname(__file__), "..",
                        "src", "repro", "configs")
    os.makedirs(base, exist_ok=True)
    for mod, spec in ARCHS.items():
        body = HEADER.format(title=spec["title"], cite=spec["cite"])
        body += "CONFIG = " + fmt(spec["CONFIG"]) + "\n\n"
        body += "REDUCED = " + fmt(spec["REDUCED"]) + "\n"
        with open(os.path.join(base, mod + ".py"), "w") as f:
            f.write(body)
        print("wrote", mod)


if __name__ == "__main__":
    main()
