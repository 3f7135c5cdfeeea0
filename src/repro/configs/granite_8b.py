"""Granite-8B code model — llama-arch dense GQA with a bias on every
projection (``attention_bias``: Q, K, V and O; ``mlp_bias``: gate, up and
down), as IBM's Granite-8B-Code configs publish it.  The embedding is the
unembedding: the model card's 8.05B parameters are 36 layers of 218.2M and
one 49,152 x 4,096 matrix (untied would be 8.26B).  [arXiv:2405.04324]"""
from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-8b",
        family="dense",
        n_layers=36,
        d_model=4096,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        qkv_bias=True,
        o_bias=True,
        mlp_bias=True,
        d_ff=14336,
        vocab_size=49152,
        tie_embeddings=True,
        rope_theta=10_000_000.0,
        max_seq_len=131072,
        source="arXiv:2405.04324",
    )
