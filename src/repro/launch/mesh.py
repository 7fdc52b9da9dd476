"""Production mesh + logical-axis → mesh-axis rule sets.

Importing this module never touches jax device state (mesh construction is
inside functions only).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def auto_axes(mesh):
    """``mesh`` with every axis in Auto mode.

    ``jax.make_mesh`` makes Explicit axes by default, and Explicit axes
    reject ``with_sharding_constraint`` and ambiguous gathers.  This
    repository pins layouts with constraints and lets the partitioner
    propagate the rest, so every mesh it shards over is Auto.
    """
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)


TP = 16  # model-parallel degree of the production mesh (both variants)


def param_rules(cfg, multi_pod: bool, serve: bool = False,
                overrides: dict | None = None) -> dict:
    """Logical param axes -> mesh axes.

    Train: TP over 'model' + ZeRO-3/FSDP over the data axes (params, grads
    and optimizer state all sharded; GSPMD all-gathers per layer inside the
    scan).  Serve: TP only (no per-token FSDP gathers).
    """
    fsdp = None if serve else dp_axes(multi_pod)
    rules = {
        "embed": fsdp,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "expert": "model",
        "expert_mlp": None,
        "lora": None,
        "layers": None,
        "lru_in": "model",  # RG-LRU recurrent gates: row-parallel default
        "lru_out": None,
    }
    if not cfg.shard_attn_heads:
        # Tiny-width archs (xlstm): replicate mixer internals, keep TP on
        # vocab + FSDP on the embed dim only (DESIGN.md §4).
        rules.update(heads=None, kv_heads=None, mlp=None)
    if overrides:
        rules.update(overrides)
    return rules


def act_rules(cfg, multi_pod: bool, batch_shardable: bool = True,
              overrides: dict | None = None) -> dict:
    dp = dp_axes(multi_pod)
    rules = {
        "batch": dp if batch_shardable else None,
        "heads_act": "model",
        "kv_heads_act": "model",
        "mlp_act": "model",
        "vocab_act": "model",
        "seq_act": None,  # 'model' under sequence parallelism (hillclimb)
        "expert": "model",
    }
    if not cfg.shard_attn_heads:
        rules.update(heads_act=None, kv_heads_act=None, mlp_act=None)
    if overrides:
        rules.update(overrides)
    return rules


def spec_of(axes: tuple, rules: dict) -> P:
    return P(*(rules.get(a) if a is not None else None for a in axes))


def specs_from_axes(axes_tree, rules: dict):
    return jax.tree.map(lambda ax: spec_of(ax, rules), axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def shardings_from_axes(mesh, axes_tree, rules: dict):
    mesh = auto_axes(mesh)
    return jax.tree.map(
        lambda ax: NamedSharding(mesh, spec_of(ax, rules)), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def batch_specs(cfg, shape_kind: str, rules: dict) -> dict:
    """PartitionSpecs for the input batch dict (batch dim over DP)."""
    b = rules.get("batch")
    specs = {}
    if cfg.input_mode == "tokens":
        specs["tokens"] = P(b, None) if shape_kind != "codebooks" else None
    if cfg.input_mode == "codebooks":
        specs["tokens"] = P(b, None, None)
    if cfg.input_mode == "embeddings":
        specs["embeddings"] = P(b, None, None)
    if cfg.pos == "mrope":
        specs["positions"] = P(None, b, None)
    if shape_kind == "train":
        specs["labels"] = P(b, None)
    return specs
