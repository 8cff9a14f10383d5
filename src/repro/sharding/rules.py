"""Logical-axis sharding rules (MaxText-style) and the mesh context.

Model code annotates activations with *logical* axis names; this module
resolves them against the current mesh (single-pod ``(data, model)`` or
multi-pod ``(pod, data, model)``).  When no mesh is active (CPU unit tests)
every annotation is a no-op, so the same model code runs everywhere.

Logical axes:
    dp      batch                 -> (pod, data) / (data,)
    tp      heads / ff / experts / vocab -> model
    fsdp    weight embed-dim ZeRO-3      -> data (only when cfg.fsdp)
    sp      sequence (long-context)      -> data
"""
from __future__ import annotations

import re
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: dict = {"mesh": None, "fsdp": False, "expert_layout": "ep"}


def set_mesh(mesh: Mesh | None, fsdp: bool = False, expert_layout: str = "ep") -> None:
    _ACTIVE["mesh"] = mesh
    _ACTIVE["fsdp"] = fsdp
    _ACTIVE["expert_layout"] = expert_layout


def current_mesh() -> Mesh | None:
    return _ACTIVE["mesh"]


def expert_layout() -> str:
    """"ep" (experts over model — train/prefill) or "tp" (per-expert tensor
    parallelism, experts replicated over model — decode/serving, where
    1-token-per-expert capacities make EP useless; §Perf cell 2)."""
    return _ACTIVE["expert_layout"]


@contextmanager
def mesh_context(mesh: Mesh | None, fsdp: bool = False, expert_layout: str = "ep"):
    prev = (_ACTIVE["mesh"], _ACTIVE["fsdp"], _ACTIVE["expert_layout"])
    set_mesh(mesh, fsdp, expert_layout)
    try:
        yield
    finally:
        set_mesh(*prev)


def _resolve(axis: str | None, mesh: Mesh) -> tuple | str | None:
    names = mesh.axis_names
    if axis is None:
        return None
    if axis == "dp":
        return ("pod", "data") if "pod" in names else ("data",)
    if axis == "tp":
        return "model"
    if axis == "sp":
        return "data"
    if axis == "fsdp":
        return "data" if _ACTIVE["fsdp"] else None
    raise ValueError(f"unknown logical axis {axis!r}")


def logical(*axes: str | None) -> P:
    """Resolve logical axis names to a PartitionSpec for the active mesh."""
    mesh = current_mesh()
    if mesh is None:
        return P()
    return P(*[_resolve(a, mesh) for a in axes])


def fitted(shape, *axes: str | None) -> P:
    """logical() + divisibility guard against a concrete shape."""
    mesh = current_mesh()
    if mesh is None:
        return P()
    return fit_spec([_resolve(a, mesh) for a in axes], shape, mesh)


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op if none).
    Axes that don't divide the corresponding dim are dropped."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = fit_spec([_resolve(a, mesh) for a in axes], x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter partition specs, by path-name rules.
#
# Conventions (all stacked params carry a leading layer dim -> None):
#   embedding (V, D)            vocab -> tp, D -> fsdp
#   unembed   (D, V)            D -> fsdp, vocab -> tp
#   wq/wk/wv  (.., D, H, hd)    D -> fsdp, H -> tp
#   wo        (.., H, hd, D)    H -> tp, D -> fsdp
#   mlp wi/wg (.., D, F)        D -> fsdp, F -> tp
#   mlp wo    (.., F, D)        F -> tp, D -> fsdp
#   experts   (.., E, D, F)     E -> tp (expert parallelism)
#   router    (.., D, E)        replicated
#   biases / norms / scalars    replicated
#   ssd/rglru small weights     replicated (elementwise channel params)
# ---------------------------------------------------------------------------

_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    # order matters — first match wins. Specs are for the TRAILING dims
    # (leading scan/layer dims padded with None automatically).
    (r"embedding$", ("tp", "fsdp")),
    (r"unembed$", ("fsdp", "tp")),
    (r"(wq|wk|wv)$", ("fsdp", "tp")),  # fused (D, H*hd)
    (r"wo_attn$", ("tp", "fsdp")),  # fused (H*hd, D)
    (r"(w_dkv|w_dq)$", ("fsdp", None)),  # MLA down-proj (D, r)
    (r"(w_uq|w_uk|w_uv)$", (None, "tp", None)),  # MLA up-proj (r, H, hd)
    (r"w_qr$", (None, "tp", None)),  # MLA rope-q (r, H, hd_r)
    (r"w_kr$", ("fsdp", None)),  # MLA rope-k (D, hd_r)
    (r"(wi|wg)$", ("fsdp", "tp")),
    (r"wo_mlp$", ("tp", "fsdp")),
    (r"experts_(wi|wg)$", ("tp", "fsdp", None)),  # (E, D, Fe) — EP + ZeRO-3
    (r"experts_wo$", ("tp", None, "fsdp")),  # (E, Fe, D)
    (r"router$", (None, None)),
    (r"in_proj(_[a-z]+)?$", ("fsdp", "tp")),  # ssm / rglru in-projections
    (r"out_proj$", ("tp", "fsdp")),
    (r".*", ()),  # everything else fully replicated
]


def _axis_size(entry, mesh) -> int:
    if entry is None:
        return 1
    if isinstance(entry, tuple):
        n = 1
        for e in entry:
            n *= mesh.shape[e]
        return n
    return mesh.shape[entry]


def fit_spec(resolved_axes, shape, mesh) -> P:
    """Drop mesh axes from dims they don't evenly divide (jit boundary
    requires exact divisibility for explicit input shardings)."""
    out = []
    for dim, entry in zip(shape, resolved_axes):
        if entry is not None and dim % _axis_size(entry, mesh) != 0:
            entry = None
        out.append(entry)
    return P(*out)


def _spec_for(path: str, leaf) -> P:
    ndim = getattr(leaf, "ndim", 0)
    shape = getattr(leaf, "shape", ())
    if _ACTIVE["expert_layout"] == "tp" and re.search(r"experts_(wi|wg|wo)$", path):
        trailing = (None, "fsdp", "tp") if re.search(r"experts_(wi|wg)$", path) else (None, "tp", "fsdp")
        t = list(trailing)[-ndim:] if ndim < 3 else list(trailing)
        axes = [None] * (ndim - len(t)) + t
        mesh = current_mesh()
        if mesh is None:
            return P()
        return fit_spec([_resolve(a, mesh) for a in axes], shape, mesh)
    for pat, trailing in _RULES:
        if re.search(pat, path):
            t = [a for a in trailing]
            if len(t) > ndim:
                t = t[-ndim:]
            axes = [None] * (ndim - len(t)) + t
            mesh = current_mesh()
            if mesh is None:
                return P()
            return fit_spec([_resolve(a, mesh) for a in axes], shape, mesh)
    return P()


def params_pspecs(params) -> object:
    """PartitionSpec pytree matching ``params`` (uses the active mesh)."""

    def walk(prefix, tree):
        if isinstance(tree, dict):
            return {k: walk(f"{prefix}/{k}", v) for k, v in tree.items()}
        return _spec_for(prefix, tree)

    return walk("", params)


def constrain_params(params):
    """Pin a (stacked) param subtree to its rule shardings. Anchors scan
    carries: without this the partitioner may choose a different sharding for
    the while-loop weight stacks and re-shard them EVERY layer (§Perf)."""
    mesh = current_mesh()
    if mesh is None:
        return params
    specs = params_pspecs(params)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: not isinstance(x, dict),
    )


_CACHE_SPECS = {
    # KV-style caches shard the TIME dim on the model axis (flash-decoding
    # style): kv-head counts (1..8) rarely divide a 16-way axis, while the
    # cache length always does; GSPMD turns the softmax reductions over the
    # sharded time dim into cheap (B,H)-sized collectives.
    "k": ("dp", "tp", None, None),
    "v": ("dp", "tp", None, None),
    "pos": ("dp", "tp"),
    "ckv": ("dp", "tp", None),
    "kr": ("dp", "tp", None),
    "h": ("dp", "tp"),
    "state": ("dp", "tp", None, None),
    "conv": ("dp", None, "tp"),
}


def cache_pspecs(cache_like):
    """PartitionSpec tree for a decode cache (leading stacked-layer dim)."""
    mesh = current_mesh()

    def walk(name, tree):
        if isinstance(tree, dict):
            return {k: walk(k, v) for k, v in tree.items()}
        if mesh is None or name == "cur":
            return P()
        trailing = _CACHE_SPECS.get(name, ())
        ndim = getattr(tree, "ndim", 0)
        axes = [None] * (ndim - len(trailing)) + [_resolve(a, mesh) for a in trailing]
        return fit_spec(axes, getattr(tree, "shape", ()), mesh)

    return walk("", cache_like)


def batch_pspecs(batch_like):
    """PartitionSpec tree for an input batch: batch dim -> dp."""
    mesh = current_mesh()

    def leaf(name, tree):
        if mesh is None:
            return P()
        dp = _resolve("dp", mesh)
        ndim = getattr(tree, "ndim", 0)
        shape = getattr(tree, "shape", ())
        if name == "positions":  # (3, B, S)
            axes = [None, dp] + [None] * (ndim - 2)
        else:
            axes = [dp] + [None] * (ndim - 1)
        return fit_spec(axes, shape, mesh)

    return {k: leaf(k, v) for k, v in batch_like.items()}


def params_shardings(params):
    mesh = current_mesh()
    if mesh is None:
        raise RuntimeError("no active mesh")
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        params_pspecs(params),
        is_leaf=lambda x: isinstance(x, P),
    )
