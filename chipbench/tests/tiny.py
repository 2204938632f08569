"""A tiny cell on the program's smoke config, for driving a run on the CPU."""

from harness.spec import Cell

DENSE = {"arch": "phi4-mini-3.8b", "smoke": True, "hidden_size": 128,
         "intermediate_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 32, "num_hidden_layers": 4, "vocab_size": 512, "padded_vocab_size": 512,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
         "torch_dtype": "float32"}
SERVE = {"kind": "serve", "clients": 3, "prompt_tokens": 16,
         "output_tokens": {"mean": 12, "sigma": 0.5, "lo": 1, "hi": 16},
         "pool_seed": 5, "pool_requests": 64, "check_requests": 3}


def serve_cell():
    return Cell(name="tiny", chips=1, config=DENSE, traffic=SERVE,
                limits={"logit_gap": 1e-3}, end_to_end=[], per_layer=[])
