"""Production mesh construction + PartitionSpec template resolution.

Importing this module never touches jax device state (the dry-run sets
``XLA_FLAGS`` before any jax import; see dryrun.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"need {need} devices for mesh {shape}, have {len(devs)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=512 before importing jax")
    return auto_mesh(shape, axes, devices=devs[:need])


def auto_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the model code
    places arrays with ``NamedSharding`` hints and lets XLA propagate them,
    which ``make_mesh``'s default ``Explicit`` axes reject (the embedding
    gather would need an ``out_sharding``)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def batch_axes_of(mesh) -> Tuple[str, ...]:
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def resolve_spec(spec: P, mesh) -> P:
    """Map template axes onto the concrete mesh: on multi-pod meshes every
    'data' entry becomes ('pod', 'data') — FSDP/batch span both axes."""
    if "pod" not in mesh.axis_names:
        return spec
    out = []
    for e in spec:
        if e == "data":
            out.append(("pod", "data"))
        elif isinstance(e, (tuple, list)):
            ee = []
            for x in e:
                ee.extend(("pod", "data") if x == "data" else (x,))
            out.append(tuple(ee))
        else:
            out.append(e)
    return P(*out)


def resolve_specs(tree, mesh):
    return jax.tree.map(lambda s: resolve_spec(s, mesh), tree,
                        is_leaf=lambda x: isinstance(x, P))


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh) -> P:
    """jit-boundary shardings must divide evenly; drop axis entries that
    don't (e.g. vocab 49155 over 16, batch 1 over 'data', 28 heads over 16).
    Internal with_sharding_constraint hints stay uneven-capable — this is
    only for in/out shardings."""
    spec = resolve_spec(spec, mesh)
    out = []
    for i, e in enumerate(spec):
        if e is None or i >= len(shape):
            out.append(e)
            continue
        axes = e if isinstance(e, (tuple, list)) else (e,)
        p = 1
        for a in axes:
            p *= mesh.shape[a]
        out.append(e if shape[i] % p == 0 else None)
    return P(*out)


def shardings(tree_of_specs, mesh, shapes_tree=None):
    """NamedShardings from spec templates; with `shapes_tree` (matching tree
    of ShapeDtypeStructs/arrays) the specs are divisibility-sanitized."""
    if shapes_tree is None:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, resolve_spec(s, mesh)),
            tree_of_specs, is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(
        lambda st, s: NamedSharding(mesh, sanitize_spec(s, st.shape, mesh)),
        shapes_tree, tree_of_specs)
