"""The program's side of a Qwen3 configuration: its ``ModelConfig`` and the
seed's weights laid out in the program's parameter tree.

The weights are the reference's (``qwen3_reference``), drawn on the device
in one jitted call, in the dtypes the program's parameter definitions ask
for.  With a mesh, that call's outputs take the program's tensor-parallel
layout (``repro.serve.shard.param_pspecs``): each device draws and keeps
only its own shard, and the values are those of the one-device call.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.models import abstract_params
from repro.models.common import ModelConfig

from . import qwen3_reference as ref

# the program's parameter paths -> the reference's leaves
_GLOBAL = {("embed", "tok"): "embed", ("embed", "head"): "head",
           ("final_norm", "scale"): "final_norm"}
_LAYER = {("mixer", "wq"): "wq", ("mixer", "wk"): "wk",
          ("mixer", "wv"): "wv", ("mixer", "wo"): "wo",
          ("mixer", "q_norm"): "q_norm", ("mixer", "k_norm"): "k_norm",
          ("norm1", "scale"): "norm1", ("norm2", "scale"): "norm2",
          ("ffn", "w_gate"): "w_gate", ("ffn", "w_up"): "w_up",
          ("ffn", "w_down"): "w_down"}


def model_config(cfg: Dict) -> ModelConfig:
    """The program's config for a configuration file's sizes."""
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qk_norm=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"])


def _keys(path):
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def make_params(cfg: Dict, mcfg: ModelConfig, seed: int, mesh=None):
    """The seed's weights in the program's tree, made on the device in one
    jitted call; with ``mesh``, born in their tensor-parallel shards."""
    shapes = abstract_params(mcfg)
    paths = [_keys(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    layers = cfg["num_hidden_layers"]

    def leaf(key, path, want):
        if path in _GLOBAL:
            v = ref.draw_global_leaf(cfg, key, _GLOBAL[path])
        elif path[0] == "segments" and path[3:] in _LAYER:
            name = _LAYER[path[3:]]
            v = jax.lax.map(lambda l: ref.draw_layer_leaf(cfg, key, l, name),
                            jnp.arange(layers))
        else:
            raise KeyError(f"program parameter {path} has no reference leaf")
        if v.shape != want.shape:
            raise ValueError(f"{path}: program wants {want.shape}, the "
                             f"reference draws {v.shape}")
        return v.astype(want.dtype)

    def build(key):
        flat = [leaf(key, p, s) for p, s in
                zip(paths, jax.tree_util.tree_leaves(shapes))]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shapes), flat)

    placed = {}
    if mesh is not None:
        from repro.serve.shard import param_pspecs
        placed["out_shardings"] = jax.tree.map(
            lambda sp: NamedSharding(mesh, sp), param_pspecs(mcfg, mesh))
    return jax.jit(build, **placed)(ref.seed_key(seed))
