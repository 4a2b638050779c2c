"""How the program holds a qwen3 configuration and its parameters.

`arch` builds the program's ArchConfig from the configuration file's
Hugging Face keys. `to_program` and `from_program` map the reference's
canonical leaves (reference/qwen3.py) to the program's parameter tree, in the
SPMD train state's scan layout (one period position, layers stacked on axis
0), and back. RMSNorm leaves are offsets from 1 on both sides.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# canonical per-layer name -> path in the program's list-layout layer tree
LAYER_PATHS = {
    "attn_norm": ("norm1",), "wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
    "wv": ("mixer", "wv"), "wo": ("mixer", "wo"), "q_norm": ("mixer", "q_norm"),
    "k_norm": ("mixer", "k_norm"), "mlp_norm": ("norm2",), "w_gate": ("ffn", "w_gate"),
    "w_up": ("ffn", "w_up"), "w_down": ("ffn", "w_down"),
}
TOP = ("embed", "final_norm", "lm_head")


def arch(cfg):
    from repro.configs.base import ArchConfig, LayerSpec

    if cfg.get("tie_word_embeddings") or cfg.get("attention_bias") or cfg.get("use_sliding_window"):
        raise ValueError("qwen3 configurations here have untied heads, no bias, no window")
    a = ArchConfig(
        arch_id=cfg["name"], family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        period=(LayerSpec("attn", attn_kind="full", ffn="dense"),), qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"])
    if a.padded_vocab != cfg["assumed"]["padded_vocab_size"]:
        raise ValueError(f"program pads the vocabulary to {a.padded_vocab}, "
                         f"the configuration assumes {cfg['assumed']['padded_vocab_size']}")
    return a


def _layer(canon, i):
    out = {}
    for name, path in LAYER_PATHS.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = canon[f"layers.{i}.{name}"]
    return out


def n_layers(canon):
    return sum(1 for k in canon if k.endswith(".wq"))


def to_program(canon):
    """Canonical leaves -> the program's params."""
    layers = [_layer(canon, i) for i in range(n_layers(canon))]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"layers": (stacked,), **{k: canon[k] for k in TOP}}


def from_program(params):
    """The program's params -> canonical leaves (arrays of any kind)."""
    out = {k: params[k] for k in TOP}
    (stacked,) = params["layers"]
    for i in range(stacked["mixer"]["wq"].shape[0]):
        for name, path in LAYER_PATHS.items():
            out[f"layers.{i}.{name}"] = _dig(stacked, path)[i]
    return out


def _dig(tree, path):
    for k in path:
        tree = tree[k]
    return tree
