"""Logical-axis sharding rules (MaxText-style).

Model parameters carry tuples of logical axis names (see models/layers.py);
a *rule set* maps logical names to mesh axes, yielding PartitionSpecs for
pjit.  Rule sets:

  fsdp_tp  -- training: weights sharded d_model over the DP axes (FSDP) and
              heads/mlp/experts/vocab over "model" (TP/EP); batch over DP.
  tp_only  -- serving: weights sharded over "model" only (no per-step FSDP
              all-gathers); batch over DP axes.
  dp_only  -- small models / debugging: weights replicated, batch over DP.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = tuple[str, ...]


def rule_set(name: str, dp_axes: Sequence[str] = ("data",),
             tp_axis: str = "model") -> dict:
    dp = tuple(dp_axes)
    if name == "fsdp_tp":
        return {
            "embed": dp, "q_heads": (tp_axis,), "kv_heads": (tp_axis,),
            "mlp": (tp_axis,), "experts": (tp_axis,), "vocab": (tp_axis,),
            "ssm_inner": (tp_axis,), "layers": (), "batch": dp, "seq": (),
        }
    if name == "tp_only":
        return {
            "embed": (), "q_heads": (tp_axis,), "kv_heads": (tp_axis,),
            "mlp": (tp_axis,), "experts": (tp_axis,), "vocab": (tp_axis,),
            "ssm_inner": (tp_axis,), "layers": (), "batch": dp, "seq": (),
        }
    if name == "dp_only":
        return {k: () for k in ("embed", "q_heads", "kv_heads", "mlp",
                                "experts", "vocab", "ssm_inner", "layers",
                                "seq")} | {"batch": dp}
    raise ValueError(f"unknown rule set {name!r}")


def spec_for(axes: tuple[str | None, ...], rules: dict,
             mesh: Mesh | None = None,
             shape: tuple[int, ...] | None = None) -> P:
    """PartitionSpec for one parameter's logical axes.

    If ``mesh``+``shape`` are given, drops mesh axes that do not divide the
    dimension (falls back to replication for that dim) and never assigns the
    same mesh axis twice."""
    used: set[str] = set()
    parts: list[Any] = []
    for i, ax in enumerate(axes):
        assigned: tuple[str, ...] = ()
        if ax is not None and ax in rules:
            cand = tuple(a for a in rules[ax] if a not in used)
            if mesh is not None and shape is not None and cand:
                n = int(np.prod([mesh.shape[a] for a in cand]))
                if shape[i] % n != 0:
                    cand = ()
            assigned = cand
        used.update(assigned)
        if len(assigned) == 0:
            parts.append(None)
        elif len(assigned) == 1:
            parts.append(assigned[0])
        else:
            parts.append(assigned)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def params_pspecs(axes_tree, rules: dict, mesh: Mesh | None = None,
                  shapes_tree=None):
    """Map a pytree of logical-axes tuples to PartitionSpecs."""
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)
    if shapes_tree is None:
        return jax.tree.map(lambda a: spec_for(a, rules, None, None),
                            axes_tree, is_leaf=is_leaf)
    return jax.tree.map(
        lambda a, s: spec_for(a, rules, mesh, tuple(s.shape)),
        axes_tree, shapes_tree, is_leaf=is_leaf)


def params_shardings(axes_tree, rules: dict, mesh: Mesh, shapes_tree=None):
    specs = params_pspecs(axes_tree, rules, mesh, shapes_tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(rules: dict) -> P:
    dp = rules["batch"]
    return P(dp if len(dp) > 1 else (dp[0] if dp else None))


def maybe_constrain(x, axes: tuple):
    """Sharding-constrain an activation when a mesh context is installed.

    ``axes`` entries: "dp" (the DP/FSDP axes), "tp" (tensor-parallel axis),
    or None.  Without explicit activation constraints GSPMD is free to
    reshard activations onto the FSDP axis mid-model, which materializes
    full-batch partial results and all-reduces them (observed: a 40 GB
    logits all-reduce in the qwen3 train probe).  No-op when no mesh
    context is set (unit tests, single device)."""
    from repro.parallel import mesh_ctx
    ctx = mesh_ctx.get_context()
    if ctx is None:
        return x
    parts = []
    for i, a in enumerate(axes):
        if a == "dp":
            names = ctx.batch_axes
        elif a == "tp":
            names = (ctx.tp_axis,)
        else:
            parts.append(None)
            continue
        n = int(np.prod([ctx.mesh.shape[m] for m in names]))
        if x.shape[i] % n != 0:     # non-divisible -> leave replicated
            parts.append(None)
        else:
            parts.append(names if len(names) > 1 else names[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*parts)))


def _axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def cache_pspecs(cache_shapes, mesh: Mesh, *, dp_axes: Sequence[str],
                 tp_axis: str, kv_axes: Sequence[str]):
    """PartitionSpecs for a serving cache pytree (keyed by leaf name).

    batch-layout k/v [L,B,Hkv,S,hd]: batch over DP; KV heads over TP when
    divisible, else the sequence dim over TP (flash-decode merge territory).
    paged k/v pages [L,NP,Hkv,slots,hd]: pages over the EMem owner axes.
    SSM states: batch over DP, heads/channels over TP when divisible.
    """
    dp, kv = tuple(dp_axes), tuple(kv_axes)
    dp_n, tp_n = _axes_size(mesh, dp), mesh.shape[tp_axis]
    dp_spec = dp if len(dp) > 1 else dp[0]
    kv_spec = kv if len(kv) > 1 else kv[0]

    def leaf_spec(path, leaf) -> P:
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = str(p.key)
                break
        shape = leaf.shape
        if name in ("k", "v", "xk", "xv"):
            l, b, hkv, s, hd = shape
            batch = dp_spec if b % dp_n == 0 else None
            if hkv % tp_n == 0:
                return P(None, batch, tp_axis, None, None)
            if s % tp_n == 0:
                return P(None, batch, None, tp_axis, None)
            return P(None, batch, None, None, None)
        if name in ("k_pages", "v_pages"):
            return P(None, kv_spec, None, None, None)
        if name == "conv":
            l, b, k_, c = shape
            batch = dp_spec if b % dp_n == 0 else None
            chan = tp_axis if c % tp_n == 0 else None
            return P(None, batch, None, chan)
        if name == "ssd":
            l, b, h, n, pdim = shape
            batch = dp_spec if b % dp_n == 0 else None
            heads = tp_axis if h % tp_n == 0 else None
            return P(None, batch, heads, None, None)
        return P()

    paths = jax.tree_util.tree_flatten_with_path(cache_shapes)[0]
    treedef = jax.tree.structure(cache_shapes)
    return jax.tree.unflatten(treedef,
                              [leaf_spec(p, l) for p, l in paths])
