"""Per-leaf kernel application over agent-stacked pytrees.

The fused error-feedback kernels (:mod:`repro.kernels.ef_update`) and the
stochastic-rounding cast (:mod:`repro.kernels.sr_cast`) grid over any
array as it lies in memory.  The algorithm layer keeps its state as
agent-stacked pytrees (leading ``n_agents`` axis per leaf).
:func:`plane_apply` is the bridge: it runs a kernel once per leaf, over all
agents of that leaf, and restores each output leaf's dtype.

Packing every leaf of a tree into one concatenated, padded plane is what
this module did first.  At model scale the TPU compiler then needed tens
of GiB of host memory for the concatenation (one 251M-parameter round did
not finish compiling within 20 GiB), and every operand paid a relayout
copy; per-leaf calls compile in seconds and copy nothing.

Time-varying topologies need no plumbing here: the comm-round engine mixes
in the pytree domain *before* the kernels run, so under a
:class:`repro.core.mixing.TopologySchedule` the round's ``wc = W_t @ c``
arrives at :func:`plane_apply` as ordinary data -- the kernel grids and
the per-shard program are all schedule-invariant (one executable per chunk
size, exactly as with a static graph).

Per-shard planes: a Mosaic kernel is one opaque call that XLA's SPMD
partitioner cannot split.  Given a mesh, :func:`plane_apply` therefore
runs the kernels *inside* ``shard_map`` with the engine's leaf specs, so every device works on its own (agent shard x
model shard) block.  The fused updates are elementwise, so the per-shard
program needs no communication at all.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = ["plane_apply"]


def plane_apply(kernel, trees: Sequence[Any], n_out: int,
                mesh=None, leaf_specs=None):
    """Run ``kernel`` over ``trees``, one leaf at a time.

    kernel: ``(j, leaf, ...) -> (leaf, ...)`` over same-shape agent-stacked
    leaves (``n_out`` outputs), where ``j`` is the leaf's index in
    tree-flatten order (the engine folds it into its stochastic-rounding
    key); ``trees``: same-structure agent-stacked pytrees.  Output ``i`` is
    restored with the leaf dtypes of ``trees[i]`` -- the engine's update
    methods return (a permutation of) their first ``n_out`` input buffers,
    and under mixed precision those buffers legitimately differ in dtype
    (f32 master params next to bf16 EF planes), so the master copy is never
    downcast.

    With ``mesh=None`` the kernels run on the global arrays (one device).
    With a mesh the same calls run inside ``shard_map`` over it, each device
    on its local block; ``leaf_specs`` are the per-leaf PartitionSpecs
    (agent axis first) that inputs and outputs keep.
    """
    structs = [jax.tree_util.tree_structure(t) for t in trees]
    if any(s != structs[0] for s in structs):
        raise ValueError(f"plane_apply needs same-structure trees, got "
                         f"{structs}")

    def local(*ts):
        groups = zip(*(jax.tree_util.tree_leaves(t) for t in ts))
        outs = [[] for _ in range(n_out)]
        for j, leaves in enumerate(groups):
            res = kernel(j, *leaves)
            for i, o in enumerate(res):
                outs[i].append(o.astype(leaves[i].dtype))
        return tuple(structs[i].unflatten(outs[i]) for i in range(n_out))

    if mesh is None:
        return local(*trees)
    if leaf_specs is None:
        raise ValueError("per-shard planes need leaf_specs with the mesh")
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(leaf_specs,) * len(trees),
                       out_specs=(leaf_specs,) * n_out, check_vma=False)
    return fn(*trees)
