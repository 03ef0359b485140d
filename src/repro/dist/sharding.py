"""Sharding rule table: logical parameter axes -> mesh axes (DESIGN.md §5).

Every parameter declares *logical* axis names in its :class:`ParamSpec`
(repro/models/spec.py); this module is the single place where logical names
meet a concrete mesh.  Rules:

  * exactly one dimension shards on ``model``, chosen by priority
    (``MODEL_PRIORITY``: experts > vocab > mlp > heads > kv > state > embed)
    among dimensions divisible by the axis size — indivisible candidates
    fall through to the next name, and if nothing divides, the parameter
    replicates.  This is why smollm's 9 heads fall back to sharding embed
    and grok's 8 experts fall back to tensor-parallel d_ff.
  * with ``opt_data_axis`` set (ZeRO / FSDP), one *additional* dimension
    shards on the data axis — the first remaining logical dimension that
    divides, never ``layers`` (the scanned layer stack must stay intact per
    device).
  * decode caches shard batch over the data axes and the sequence dimension
    over ``model`` (flash-decoding), via :func:`cache_pspecs`.

The table is pure shape arithmetic — it works on a real ``jax.Mesh`` or any
stand-in exposing ``axis_names`` and ``devices.shape`` (tests use a fake),
and never touches device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.models.spec import ParamSpec, is_spec

# Priority for the single model-parallel dimension.  "layers" is absent by
# design: the scanned layer stack is never sharded.
MODEL_PRIORITY: Tuple[str, ...] = (
    "experts", "vocab", "mlp", "heads", "kv", "state", "embed")


def mesh_axis_size(mesh, axis: str) -> int:
    """Size of a named mesh axis (1 if the mesh does not have it)."""
    names = tuple(mesh.axis_names)
    if axis not in names:
        return 1
    i = names.index(axis)
    if hasattr(mesh, "devices"):  # jax.Mesh or test stand-in
        return int(mesh.devices.shape[i])
    return int(tuple(mesh.axis_sizes)[i])  # AbstractMesh


def ambient_mesh():
    """The mesh activations should be pinned against, or None.

    That is the mesh installed by ``jax.set_mesh``; with none installed,
    model-side pinning helpers become no-ops.
    """
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def batch_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel mesh axes, outermost first (pod crosses DCI)."""
    return tuple(a for a in ("pod", "data") if a in tuple(mesh.axis_names))


def spec_pspec(spec: ParamSpec, mesh, *, opt_data_axis: Optional[str] = None,
               model_axis: str = "model") -> P:
    """PartitionSpec for one parameter under the rule table."""
    assign = [None] * len(spec.shape)
    msize = mesh_axis_size(mesh, model_axis)
    if msize > 1:
        for name in MODEL_PRIORITY:
            hit = [
                i for i, lg in enumerate(spec.logical)
                if lg == name and spec.shape[i] % msize == 0
                and spec.shape[i] >= msize
            ]
            if hit:
                assign[hit[0]] = model_axis
                break
    if opt_data_axis is not None:
        dsize = mesh_axis_size(mesh, opt_data_axis)
        if dsize > 1:
            for i, lg in enumerate(spec.logical):
                if (lg is not None and lg != "layers" and assign[i] is None
                        and spec.shape[i] % dsize == 0
                        and spec.shape[i] >= dsize):
                    assign[i] = opt_data_axis
                    break
    return P(*assign)


def param_pspecs(spec_tree, mesh, *, opt_data_axis: Optional[str] = None):
    """PartitionSpec pytree for a ParamSpec tree."""
    return jax.tree.map(
        lambda s: spec_pspec(s, mesh, opt_data_axis=opt_data_axis),
        spec_tree, is_leaf=is_spec,
    )


def cache_pspecs(cache_abs, mesh, *, batch: int, seq_len: int,
                 model_axis: str = "model"):
    """Decode-cache PartitionSpecs: batch over data axes, sequence over
    ``model`` (flash-decoding).  Dimensions are recognized by size — cache
    layouts vary per architecture but batch/seq extents are unambiguous.
    """
    daxes = batch_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh_axis_size(mesh, a)
    dspec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    msize = mesh_axis_size(mesh, model_axis)

    def one(x):
        assign = [None] * len(x.shape)
        for i, d in enumerate(x.shape):
            if d == batch and dsize > 1 and d % dsize == 0:
                assign[i] = dspec
                break
        for i, d in enumerate(x.shape):
            if (assign[i] is None and d == seq_len and msize > 1
                    and d % msize == 0):
                assign[i] = model_axis
                break
        return P(*assign)

    return jax.tree.map(one, cache_abs)
