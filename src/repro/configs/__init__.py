"""Config registry: the 10 assigned archs + shape table + input specs."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ENCODER_ONLY_ARCHS,
    FULL_ATTENTION_ARCHS,
    SHAPES,
    ModelConfig,
    ShapeConfig,
    cell_supported,
)

_MODULES = {
    "internvl2-1b": "internvl2_1b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "dbrx-132b": "dbrx_132b",
    "yi-6b": "yi_6b",
    "gemma2-27b": "gemma2_27b",
    "qwen2-7b": "qwen2_7b",
    "llama3-405b": "llama3_405b",
    "hubert-xlarge": "hubert_xlarge",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "lfm2-8b-a1b": "lfm2_8b_a1b",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.reduced() if reduced else mod.CONFIG


def input_specs(cfg: ModelConfig, shape: ShapeConfig, batch_override: int | None = None,
                seq_override: int | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for the model inputs of a cell.

    train/prefill: token (or stub-embedding) batch [+ targets for train].
    decode: one new token per sequence + the KV/SSM cache sized to seq_len.
    """
    from repro.models import transformer as tf  # local import to avoid cycles

    b = batch_override or shape.global_batch
    s = seq_override or shape.seq_len
    f = jnp.dtype(cfg.compute_dtype)
    if cfg.input_mode == "tokens":
        inputs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    else:
        inputs = {"embeddings": jax.ShapeDtypeStruct((b, s, cfg.d_model), f)}
    if shape.kind == "train":
        inputs["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return {"batch": inputs}
    if shape.kind == "prefill":
        return {"batch": inputs}
    # decode: single token + cache
    if cfg.input_mode == "tokens":
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    else:
        tok = jax.ShapeDtypeStruct((b, 1, cfg.d_model), f)
    cache = tf.cache_specs(cfg, b, s)
    return {"tokens": tok, "cache": cache}


__all__ = [
    "ModelConfig",
    "ShapeConfig",
    "SHAPES",
    "ARCH_NAMES",
    "FULL_ATTENTION_ARCHS",
    "ENCODER_ONLY_ARCHS",
    "get_config",
    "cell_supported",
    "input_specs",
]
