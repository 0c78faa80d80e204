"""Divisibility-aware logical-axis → PartitionSpec resolver.

Every tensor carries a tuple of *logical* axis names (one per dim); the
resolver maps them onto mesh axes, dropping or shrinking the mapping whenever
the dim is not divisible by the mesh-axis product or the mesh axis was already
consumed by an earlier dim of the same tensor.  This single mechanism handles
all ten architectures (e.g. mixtral's 8 experts on a 16-way model axis simply
fall through to TP-sharding of d_ff).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh-axis alternatives.  Each value is a tuple of
# ALTERNATIVE tuples tried in order (first divisible wins); a plain tuple of
# strings is treated as a single alternative whose prefixes may shrink.
#
# DEFAULT_RULES = the TP strategy (serving, and huge-model training):
#   batch over (pod, data); weights FSDP(data) × TP(model).
DEFAULT_RULES: Dict[Optional[str], Tuple] = {
    "batch": ("pod", "data"),
    "fsdp": ("data",),
    "fsdp+": ("pod", "data"),     # ZeRO-1-across-pods (optimizer state)
    "tp": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "seq": ("data",),
    "sp": ("model",),             # Megatron-style sequence parallelism
    "layer": (),
    None: (),
}

# FSDP strategy (training of the ≤15B dense archs and MoE training): no
# tensor parallelism — batch is sharded over every mesh axis (falling back to
# (data, model) when the pod axis does not divide), weights are ZeRO-3 over
# (data, model); experts stay on 'model' (the MoE shard_map does EP inside).
FSDP_RULES: Dict[Optional[str], Tuple] = {
    "batch": (("pod", "data", "model"), ("data", "model"), ("pod", "data"),
              ("data",)),
    "fsdp": (("data", "model"), ("data",)),
    "fsdp+": (("pod", "data", "model"), ("pod", "data"), ("data", "model"),
              ("data",)),
    "tp": (),
    "vocab": ("model",),
    "expert": ("model",),
    "seq": ("data",),
    "sp": (),
    "layer": (),
    None: (),
}

# Replica strategy (serving of sub-chip-scale models, e.g. mamba2-780m):
# weights fully replicated, batch over (pod, data); the model axis holds
# independent serving replicas — zero collectives on the critical path.
REPLICA_RULES: Dict[Optional[str], Tuple] = {
    "batch": ("pod", "data"),
    "fsdp": (),
    "fsdp+": (),
    "tp": (),
    "vocab": (),
    "expert": (),
    "seq": ("data",),
    "sp": (),
    "layer": (),
    None: (),
}

STRATEGIES = {"tp": DEFAULT_RULES, "fsdp": FSDP_RULES, "replica": REPLICA_RULES}


def _alternatives(entry) -> Tuple[Tuple[str, ...], ...]:
    if not entry:
        return ()
    if isinstance(entry[0], str):   # plain tuple -> its prefixes
        return tuple(tuple(entry[:k]) for k in range(len(entry), 0, -1))
    return tuple(tuple(alt) for alt in entry)   # explicit alternatives, as-is


def spec_for_logical(logical: Sequence[Optional[str]],
                     shape: Sequence[int],
                     mesh: Mesh,
                     rules: Optional[Dict] = None) -> P:
    rules = rules or DEFAULT_RULES
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical):
        chosen: Tuple[str, ...] = ()
        for alt in _alternatives(rules.get(name, ())):
            sub = tuple(a for a in alt if a in mesh.shape and a not in used)
            if len(sub) != len(alt):
                continue
            size = math.prod(mesh.shape[a] for a in sub)
            if size > 1 and dim % size == 0:
                chosen = sub
                break
        if chosen:
            used.update(chosen)
            parts.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            parts.append(None)
    return P(*parts)


def tree_shardings(logical_tree, shape_tree, mesh: Mesh, rules=None):
    """Map matching pytrees of logical tuples + shaped values -> NamedShardings."""
    return jax.tree.map(
        lambda lg, sh: NamedSharding(
            mesh, spec_for_logical(lg, sh.shape, mesh, rules)),
        logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def make_act_sharder(mesh: Optional[Mesh], rules=None):
    """Returns hook(x, logical) applying a with_sharding_constraint (no-op off-mesh)."""
    if mesh is None:
        return lambda x, logical: x

    def hook(x, logical):
        spec = spec_for_logical(logical, x.shape, mesh, rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return hook


# ----------------------------------------------------------------------
def batch_logical(cfg, shape_kind: str, long_context: bool = False) -> Dict:
    """Logical axes for each input-batch leaf."""
    out = {}
    if shape_kind == "train":
        out["tokens"] = ("batch", None)
        out["labels"] = ("batch", None)
    elif shape_kind == "prefill":
        out["tokens"] = ("batch", None)
    elif shape_kind == "decode":
        out["token"] = ("batch",)
    if cfg.family == "encdec" and shape_kind in ("train", "prefill"):
        out["frames"] = ("batch", None, None)
    if cfg.family == "vlm" and shape_kind in ("train", "prefill"):
        out["patches"] = ("batch", None, None)
    return out


def cache_logical(cfg, long_context: bool = False) -> Dict:
    """Logical axes for the decode-cache leaves (KV seq-sharded in long mode)."""
    seq_ax = "seq" if long_context else None
    out = {}
    if cfg.family in ("dense", "moe", "encdec"):
        out["k"] = ("layer", "batch", seq_ax, "tp", None)
        out["v"] = ("layer", "batch", seq_ax, "tp", None)
    if cfg.family == "vlm":
        out["k"] = ("layer", None, "batch", seq_ax, "tp", None)
        out["v"] = ("layer", None, "batch", seq_ax, "tp", None)
    if cfg.family == "ssm":
        out["state"] = ("layer", "batch", "tp", None, None)
        out["conv"] = ("layer", "batch", None, "tp")
    if cfg.family == "hybrid":
        out["k"] = ("layer", "batch", seq_ax, "tp", None)
        out["v"] = ("layer", "batch", seq_ax, "tp", None)
        out["state"] = ("layer", None, "batch", "tp", None, None)
        out["conv"] = ("layer", None, "batch", None, "tp")
    if cfg.family == "encdec":
        out["ck"] = ("layer", "batch", None, "tp", None)
        out["cv"] = ("layer", "batch", None, "tp", None)
    if cfg.family == "vlm":
        out["ck"] = ("layer", "batch", None, "tp", None)
        out["cv"] = ("layer", "batch", None, "tp", None)
    return out
