"""Rules-based logical-axis sharding.

Model code annotates activations with *logical* axis names
(``shd.logical(x, "batch", None, "model")``); a rules table maps logical
names to physical mesh axes. Outside any rules context the annotations are
identity — the same model code runs on one CPU device (tests) and on the
(pod, data, model) production mesh (dry-run / deployment) unchanged.

Parameter sharding is by naming convention (`param_pspec`): the tree path
of each weight decides its PartitionSpec (e.g. ``wq [D, H*Dh]`` is
(fsdp-in, tensor-out)-sharded). Stacked scan layers get a leading None.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, tuple[str, ...]]

_state = threading.local()


# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

#: Default logical->physical mapping for the production mesh.
#: "batch" covers the pod axis too when present (pure DP across pods).
DEFAULT_RULES: dict[str, Axis] = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),  # flattened (batch*seq) token dim
    "dp": ("pod", "data"),      # strictly data axes (MoE dispatch groups)
    "seq": None,           # sequence-parallel off in the baseline
    "model": "model",      # TP: attention heads-merged dim, d_ff, vocab
    "kv_seq": "model",     # KV-cache seq axis (split-KV decode layout)
    "fsdp": ("pod", "data"),  # ZeRO-3 weight sharding; spans pods on the
                              # multi-pod mesh (multislice FSDP over DCN)
    "expert": "model",     # EP shares the model axis
    "edge": ("pod", "data"),   # GNN edge-parallel
    "node": None,          # GNN node features replicated in the baseline
    "table": ("pod", "data", "model"),  # recsys embedding rows (all devices)
    "candidate": "model",  # retrieval candidate scoring
    "request": ("pod", "data"),  # routing dispatch batch (serving fan-out)
}


#: Training adds Megatron-SP-style sequence sharding of the residual
#: stream at layer boundaries (saved scan carries shrink 16x).
TRAIN_RULES: dict[str, Axis] = {**DEFAULT_RULES, "seq": "model",
                                "tokens": ("pod", "data", "model")}


def _mesh_axes(mesh: Mesh) -> set[str]:
    return set(mesh.axis_names)


def _resolve(axis: Axis, mesh: Mesh) -> Axis:
    """Drop physical axes the mesh doesn't have (e.g. 'pod' single-pod)."""
    if axis is None:
        return None
    names = _mesh_axes(mesh)
    if isinstance(axis, str):
        return axis if axis in names else None
    kept = tuple(a for a in axis if a in names)
    return kept if kept else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict[str, Axis]] = None):
    """Activate sharding annotations for model code traced inside."""
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, dict(DEFAULT_RULES if rules is None else rules))
    try:
        with mesh:
            yield
    finally:
        _state.ctx = prev


def active_mesh() -> Optional[Mesh]:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def mesh_axis_size(name: str) -> int:
    """Size of a physical mesh axis under the active mesh (1 if absent)."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(name, 1)


def spec_for(*logical_axes: Optional[str]) -> Optional[P]:
    """PartitionSpec for logical axes under the active rules, else None."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return None
    mesh, rules = ctx
    phys = []
    for ax in logical_axes:
        if ax is None:
            phys.append(None)
        else:
            if ax not in rules:
                raise KeyError(f"unknown logical axis {ax!r}; rules: {sorted(rules)}")
            phys.append(_resolve(rules[ax], mesh))
    return P(*phys)


def logical(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names; identity w/o rules.

    Axes that don't divide the corresponding dim are dropped (batch==1
    decode, 47-class heads, ...) so the same model code traces for every
    cell shape.
    """
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, _ = ctx
    spec = spec_for(*logical_axes)
    guarded = tuple(
        ax if (ax is None or dim % _axis_size(mesh, ax) == 0) else None
        for ax, dim in zip(tuple(spec), x.shape))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*guarded)))


def named_sharding(*logical_axes: Optional[str]) -> Optional[NamedSharding]:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return None
    mesh, _ = ctx
    return NamedSharding(mesh, spec_for(*logical_axes))


# ---------------------------------------------------------------------------
# Parameter sharding by tree-path convention
# ---------------------------------------------------------------------------

#: (path regex, logical axes per trailing dim). Longest match wins; a
#: leading scan/stack dim (params under "layers" or per-table stacks) is
#: handled by left-padding Nones to the array rank.
_PARAM_RULES: list[tuple[str, tuple[Optional[str], ...]]] = [
    (r"embed$", ("model", "fsdp")),          # [V, D] vocab-TP + fsdp
    (r"lm_head$", ("fsdp", "model")),        # [D, V]
    (r"attn/w[qkv]$", ("fsdp", "model")),    # [D, H*Dh] col-parallel
    (r"attn/wo$", ("model", "fsdp")),        # [H*Dh, D] row-parallel
    (r"(mlp|shared)/w_(gate|up)$", ("fsdp", "model")),
    (r"(mlp|shared)/w_down$", ("model", "fsdp")),
    (r"moe/router$", ("fsdp", None)),
    # EP owns the model axis; the within-expert dims use fsdp only
    (r"moe/w_(gate|up)$", ("expert", "fsdp", None)),     # [E, D, F]
    (r"moe/w_down$", ("expert", None, "fsdp")),          # [E, F, D]
    (r"ln_\w+$", (None,)),
    # --- GNN: weights are tiny (8x8 heads) — replicate ---
    (r"gnn/", ()),
    # --- recsys ---
    (r"tables$", ("table", None)),            # [sum_vocab, dim] row-sharded
    (r"fm/w1$", ("table", None)),             # first-order FM weights
    (r"(bot|top|deep|mlp|cross|fm|gru|augru)\w*/w\d*$", ("fsdp", "model")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(p.name)
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if isinstance(axis, str):
        return sizes.get(axis, 1)
    n = 1
    for a in axis:
        n *= sizes.get(a, 1)
    return n


def param_pspec(path, leaf) -> P:
    """PartitionSpec for one parameter leaf by its tree path.

    jit in_shardings require every sharded dim to divide evenly; axes whose
    size doesn't divide the dim are dropped (e.g. a [64, 47] GAT head or a
    [13, 512] DLRM bottom-MLP stays replicated on that dim).
    """
    name = _path_str(path)
    shape = tuple(getattr(leaf, "shape", ()))
    ndim = len(shape)
    for pattern, axes in _PARAM_RULES:
        if re.search(pattern, name):
            ctx = getattr(_state, "ctx", None)
            if ctx is None:
                return P()
            mesh, rules = ctx
            phys = tuple(
                _resolve(rules.get(a), mesh) if a is not None else None
                for a in axes)
            pad = ndim - len(phys)
            if pad < 0:  # rank-deficient leaf (e.g. scalar) — replicate
                return P()
            full = (None,) * pad + phys
            guarded = tuple(
                ax if (ax is not None and dim % _axis_size(mesh, ax) == 0)
                else None
                for ax, dim in zip(full, shape))
            return P(*guarded)
    return P()  # replicate by default (norm scales, biases, scalars)


def tree_pspecs(tree: Any) -> Any:
    """Pytree of PartitionSpecs matching ``tree``'s structure."""
    return jax.tree_util.tree_map_with_path(lambda p, l: param_pspec(p, l), tree)


def tree_shardings(tree: Any, mesh: Optional[Mesh] = None) -> Any:
    mesh = mesh or active_mesh()
    if mesh is None:
        raise RuntimeError("no active mesh; wrap in shd.use_mesh(mesh)")
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, param_pspec(p, l)), tree)
