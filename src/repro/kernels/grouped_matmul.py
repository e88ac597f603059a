"""Grouped matrix product on the TPU: the Pallas megablox ``gmm`` kernel
with tiles sized for the expert products of a dropless MoE layer.

Rows ``[o_g, o_g + group_sizes[g])`` of ``lhs`` (M, K) are multiplied by
``rhs[g]`` (K, N). The kernel visits only the row tiles its groups cover, so
rows past the last group are neither read nor written (their output is
undefined), and a group whose rows start a tile reads its weights once per
tile of its rows. Oracle: ``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas.ops.tpu.megablox import gmm

#: the largest contraction and output tile: a (2048, 2048) bfloat16 weight
#: block, double-buffered, stays well inside the 16 MiB of scoped VMEM
MAX_TILE_KN = 2048


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   out_dtype, tile_rows: int, interpret: bool = False) -> jax.Array:
    """``tile_rows`` must divide M."""
    k, n = rhs.shape[1:]
    return gmm(lhs, rhs, group_sizes, preferred_element_type=out_dtype,
               tiling=(tile_rows, min(k, MAX_TILE_KN), min(n, MAX_TILE_KN)),
               interpret=interpret)
