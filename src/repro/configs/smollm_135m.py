"""smollm-135m [dense]: 30L d_model=576 9H (GQA kv=3) d_ff=1536 vocab=49152 —
llama-arch small, SwiGLU, RMSNorm (eps 1e-5), RoPE theta 10,000, tied
embedding, 2,048-token context. [hf:HuggingFaceTB/SmolLM-135M]"""
from repro.config import ArchSpec, ModelConfig, register_arch

CONFIG = ModelConfig(
    name="smollm-135m",
    family="dense",
    num_layers=30,
    d_model=576,
    num_heads=9,
    num_kv_heads=3,
    d_ff=1536,
    vocab_size=49152,
    max_seq_len=2048,
    rope_theta=10000.0,
    norm_eps=1e-5,
    act="silu",
    tie_embeddings=True,
)

REDUCED = CONFIG.replace(
    name="smollm-reduced",
    num_layers=2, d_model=96, num_heads=3, num_kv_heads=3, d_ff=256,
    vocab_size=512,
)

register_arch(ArchSpec(
    arch_id="smollm-135m",
    config=CONFIG,
    reduced=REDUCED,
    source="hf:HuggingFaceTB/SmolLM-135M",
    notes="~135M params: the end-to-end CPU-trainable arch (examples use a "
          "trimmed variant). long_500k via sliding_window variant.",
))
