"""Pallas TPU kernel: group-by aggregation via one-hot MXU contraction.

The paper's Alg. 3 hot loop scatters each item's aggregates into a hash
table.  TPUs have no efficient scatter; the TPU-native adaptation
(DESIGN.md §3) turns the scatter into a matmul:

    sums[G, A]  += onehot(gids)[N, G]ᵀ @ (vals·w)[N, A]

which runs on the MXU.  The [G, A] (+ sumsq, matched) accumulators stay
resident in VMEM across grid steps; each grid step streams one [block, ...]
tile of items.  G is the *padded* group-table size (hash-bucketed for large
domains, e.g. the paper's 1M-group Q1 — see repro/core/gla.py).

Tiling: items stream as [block_rows, A] row blocks (unlike chunk_agg's
[R, 128] lane tiles — here the lane dim carries the A aggregates, and the
one-hot is built per block with a broadcasted_iota over G).  The ops.py
wrapper pads G to a multiple of 128 (the one-hot's lane dim) and A to a
multiple of 8 (the [G, A] output sublane pairing), so both matmul operand
shapes are MXU-aligned; ``matched`` keeps its [G, 1] layout (a single
lane-dim column — tolerated, and sliced off by the wrapper anyway).

Bitwise guarantee: driven with ``block_rows`` == chunk length (as
``core/scan.py::kernel_round_delta`` does), accumulation runs chunk by
chunk in the scan's association order and states equal the group-by
scan's (``gla.group_partials``) bit-for-bit on XLA:CPU.  The fused
round-slice kernel (:mod:`repro.kernels.fused_agg`, DESIGN.md §12) extends the same
guarantee to scalars and in-kernel decode; authoring rules in
docs/KERNELS.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _group_body(vals_ref, weight_ref, gids_ref, sums_ref, sumsqs_ref,
                matched_ref, *, num_groups: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        sumsqs_ref[...] = jnp.zeros_like(sumsqs_ref)
        matched_ref[...] = jnp.zeros_like(matched_ref)

    v = vals_ref[...].astype(jnp.float32)        # [B, A]
    w = weight_ref[...].astype(jnp.float32)      # [B, 1]
    g = gids_ref[...]                            # [B, 1] int32
    B = v.shape[0]
    # one-hot on the fly: [B, G]
    iota = jax.lax.broadcasted_iota(jnp.int32, (B, num_groups), 1)
    onehot = (g == iota).astype(jnp.float32)
    vw = v * w                                    # [B, A]
    sums_ref[...] += jnp.dot(onehot.T, vw, preferred_element_type=jnp.float32)
    sumsqs_ref[...] += jnp.dot(onehot.T, v * vw,
                               preferred_element_type=jnp.float32)
    matched_ref[...] += jnp.dot(onehot.T, w, preferred_element_type=jnp.float32)


def group_agg_kernel(vals, weight, gids, *, num_groups: int,
                     block_rows: int = 512, interpret: bool = False):
    """vals [N, A], weight [N, 1], gids [N, 1] -> (sums, sumsqs [G, A], matched [G, 1]).

    N % block_rows == 0; the ops.py wrapper pads num_groups to a multiple
    of 128 and A to a multiple of 8 before calling (MXU alignment).
    """
    N, A = vals.shape
    assert N % block_rows == 0
    grid = (N // block_rows,)
    vspec = pl.BlockSpec((block_rows, A), lambda i: (i, 0))
    wspec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    out_ga = pl.BlockSpec((num_groups, A), lambda i: (0, 0))
    out_g1 = pl.BlockSpec((num_groups, 1), lambda i: (0, 0))
    import functools
    return pl.pallas_call(
        functools.partial(_group_body, num_groups=num_groups),
        grid=grid,
        in_specs=[vspec, wspec, wspec],
        out_specs=[out_ga, out_ga, out_g1],
        out_shape=[
            jax.ShapeDtypeStruct((num_groups, A), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, A), jnp.float32),
            jax.ShapeDtypeStruct((num_groups, 1), jnp.float32),
        ],
        interpret=interpret,
    )(vals, weight, gids)
