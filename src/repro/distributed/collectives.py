"""Collective helpers built on shard_map: distributed top-k merge,
hierarchical (pod-aware) gradient reduction with optional compression.

These are the *explicit* collective paths; most of the framework relies on
GSPMD-propagated collectives, but (a) the retrieval top-k push-down and
(b) pod-aware compressed DP-reduce are structured communication patterns
worth owning — both are §Perf levers measured in EXPERIMENTS.md.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def distributed_topk(scores_local: jax.Array, base_offset: jax.Array,
                     k: int, axis: str):
    """Inside shard_map: local [B, k] heap -> global top-k.  Wire cost
    O(B*k*shards), the push-down that makes sharded MIPS scale."""
    vals, idx = jax.lax.top_k(scores_local, k)
    idx = idx + base_offset
    all_v = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
    all_i = jax.lax.all_gather(idx, axis, axis=1, tiled=True)
    v, pos = jax.lax.top_k(all_v, k)
    return v, jnp.take_along_axis(all_i, pos, axis=1)


def hierarchical_psum(x: jax.Array, intra_axis: str, inter_axis: Optional[str],
                      compress=None):
    """Two-level gradient reduction: full-precision psum over the intra-pod
    ICI axis, then (optionally compressed) psum over the cross-pod DCN axis.
    ``compress``: fn x -> x (e.g. int8 round-trip) applied before the slow
    hop — the classic bandwidth-tiering trick."""
    x = jax.lax.psum(x, intra_axis)
    if inter_axis is not None:
        if compress is not None:
            x = compress(x)
        x = jax.lax.psum(x, inter_axis)
    return x


def dp_allreduce_grads(grads, mesh, dp_axes=("pod", "data"), compress=None):
    """Explicit DP gradient all-reduce via shard_map (the implicit GSPMD
    path fuses this into the train step; the explicit path exists so
    compression can intercept the cross-pod hop)."""
    present = [a for a in dp_axes if a in mesh.axis_names]
    if not present:
        return grads
    intra = present[-1]
    inter = present[0] if len(present) > 1 else None

    def body(g):
        return jax.tree.map(
            lambda t: hierarchical_psum(t, intra, inter, compress) /
            functools.reduce(lambda a, b: a * b,
                             [dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
                              for ax in present], 1),
            g)

    spec = jax.tree.map(lambda _: P(*[None]), grads)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(grads)
