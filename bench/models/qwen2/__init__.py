"""The Qwen2 architecture (Qwen1.5 checkpoints): a dense decoder with
biases on q, k and v, rotate-half RoPE, SwiGLU and RMSNorm."""

from bench.models.qwen2 import reference, weights
from bench.models.qwen2.weights import Dims

__all__ = ["Dims", "model_config", "reference", "weights"]


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a Qwen2 configuration file."""
    from repro.configs.base import ModelConfig
    m = Dims.of(conf)
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, d_ff=m.ffn,
        vocab_size=m.vocab, head_dim=m.head_dim, qkv_bias=True,
        rope_theta=m.theta, norm_eps=m.eps,
        tie_embeddings=conf["tie_word_embeddings"])
