"""A tiny cell on the program's smoke granite-4.0-h config (one whole period
of the layer pattern), for driving the hybrid loop on the CPU."""

from harness.spec import Cell

GRANITE_H = {
    "arch": "granite-4.0-h-micro", "smoke": True, "hidden_size": 128,
    "intermediate_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "num_hidden_layers": 10, "vocab_size": 512, "padded_vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "torch_dtype": "float32", "position_embedding_type": "nope",
    "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
    "mamba_d_state": 16, "mamba_d_head": 32, "mamba_n_heads": 8, "mamba_expand": 2,
    "mamba_d_conv": 4, "mamba_chunk_size": 32, "mamba_n_groups": 1,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "attention_multiplier": 0.015625, "logits_scaling": 8.0,
}
SERVE = {"kind": "serve_hybrid", "clients": 3, "prompt_tokens": 40,
         "output_tokens": {"mean": 12, "sigma": 0.5, "lo": 1, "hi": 16},
         "pool_seed": 5, "pool_requests": 64, "check_requests": 3}


def serve_cell():
    return Cell(name="tiny-hybrid", chips=1, config=GRANITE_H, traffic=SERVE,
                limits={"logit_gap": 1e-3}, end_to_end=[], per_layer=[])
