"""The program's decoder (``repro.models.transformer``) at a
configuration's sizes, float32 and remat per layer, as ``launch/train.py``
trains it."""
from __future__ import annotations


def arch_config(config: dict):
    import jax.numpy as jnp

    from repro.models.layers import AttnCfg
    from repro.models.transformer import ArchConfig

    d, h = config["hidden_size"], config["num_attention_heads"]
    return ArchConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=d,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        attn=AttnCfg(kind="gqa", num_heads=h,
                     num_kv_heads=config["num_key_value_heads"],
                     head_dim=d // h, rope_theta=float(config["rope_theta"])),
        block_pattern=("attn",), mlp_kind="dense", act="swiglu",
        tie_embeddings=True, param_dtype=jnp.float32,
        norm_eps=float(config["layer_norm_eps"]), remat=True)


def grad_fn(config: dict):
    from repro.models import transformer as T

    return T.make_grad_fn(arch_config(config))
