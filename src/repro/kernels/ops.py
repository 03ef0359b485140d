"""Jit'd wrappers around the Pallas kernels.

Handle layout (flat -> [R, 128] lane tiles), padding, backend dispatch
(interpret=True on CPU — the kernels target TPU), and reduction of
lane-partial accumulators.  Semantics == repro.kernels.ref oracles.

Padding here follows the MXU discipline of docs/KERNELS.md §3: lane dims
pad to 128, sublane dims to 8, padded rows are value-inert (weight 0,
in-range gid), and outputs are sliced back so padding never escapes this
package.  These wrappers serve the legacy ``kernel_cols`` contract; the
fused ``FusedSpec`` dispatch lives in :mod:`repro.kernels.fused_agg`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import chunk_agg as _ck
from repro.kernels import group_agg as _gk
from repro.kernels import ref as _ref

LANES = _ck.LANES


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x, multiple, fill=0):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad:
        x = jnp.concatenate([x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)])
    return x


def _to_tiles(x, block_rows):
    """[N] -> [R, 128] with R % block_rows == 0 (zero padded)."""
    x = _pad_rows(x, LANES)
    x = x.reshape(-1, LANES)
    return _pad_rows(x, block_rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chunk_agg(vals, weight, mask, *, block_rows: int = 256, interpret=None):
    """Fused aggregate over a flat chunk -> [4] f32 (sum, sumsq, scanned, matched).

    vals/weight/mask: [N] any numeric dtype (cast to f32; zero-padded to
    [R, 128] lane tiles, R a multiple of ``block_rows``).  Lane partials
    are reduced here, so the result is interchangeable — not bitwise —
    with the flat mul-reduce (docs/KERNELS.md §2).
    """
    interpret = _interpret_default() if interpret is None else interpret
    v = _to_tiles(vals.astype(jnp.float32), block_rows)
    w = _to_tiles(weight.astype(jnp.float32), block_rows)
    m = _to_tiles(mask.astype(jnp.float32), block_rows)
    acc = _ck.chunk_agg_kernel(v, w, m, block_rows=block_rows,
                               interpret=interpret)
    return jnp.sum(acc[:4], axis=1)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def shard_chunk_partials(vals, weight, mask, *, block_rows: int = 256,
                         interpret=None):
    """Per-chunk partials for a whole shard in one kernel dispatch.

    vals/weight/mask: [C, L] -> [C, 4] f32 (sum, sumsq, scanned, matched)
    per chunk.  Used by the engine's ``emit="kernel"`` path (the snapshot
    prefix states are the cumsum of these rows for additive GLAs).

    Legacy scalar dispatch: per-chunk lane partials make the states
    interchangeable-not-bitwise with the scan path.  GLAs publishing a
    ``FusedSpec`` route through ``fused_agg.fused_prefix_states`` instead,
    which is bitwise (DESIGN.md §12).
    """
    interpret = _interpret_default() if interpret is None else interpret
    C, L = vals.shape

    def tiles(x):
        x = x.astype(jnp.float32)
        pad = (-L) % LANES
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((C, pad), jnp.float32)], axis=1)
        return x.reshape(C, -1, LANES)

    v, w, m = tiles(vals), tiles(weight), tiles(mask)
    R = v.shape[1]
    br = min(block_rows, R)
    while R % br:
        br -= 1
    acc = _ck.shard_agg_kernel(v, w, m, block_rows=br, interpret=interpret)
    return jnp.sum(acc[:, :4, :], axis=2)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def q6_agg(params, shipdate, discount, quantity, extendedprice, mask,
           *, block_rows: int = 256, interpret=None):
    """Fully fused Q6: params [>=5] f32, flat columns -> [4] f32."""
    interpret = _interpret_default() if interpret is None else interpret
    p = jnp.zeros((1, 8), jnp.float32).at[0, : params.shape[0]].set(params)
    tiles = [
        _to_tiles(c.astype(jnp.float32), block_rows)
        for c in (shipdate, discount, quantity, extendedprice, mask)
    ]
    acc = _ck.q6_agg_kernel(p, *tiles, block_rows=block_rows,
                            interpret=interpret)
    return jnp.sum(acc[:4], axis=1)


@functools.partial(jax.jit,
                   static_argnames=("num_groups", "block_rows", "interpret"))
def group_agg(vals, weight, gids, *, num_groups: int, block_rows: int = 512,
              interpret=None):
    """Group-by aggregate.

    vals [N] or [N, A]; weight [N]; gids [N] int32.
    returns (sums [G, A], sumsqs [G, A], matched [G]) f32 — unpadded G/A.

    MXU alignment (group_agg.py contract): G is padded to a multiple of 128
    (the one-hot ``[B, G]`` lane dim) and A to a multiple of 8 even when
    A == 1 (the ``[G, A]`` output sublane pairing); results are sliced back
    to the unpadded shapes.  Padded group columns receive no items (gids are
    in-range) and padded agg columns are zero-filled, so the padding is
    value-inert.

    Bitwise guarantee: with ``block_rows`` pinned to the chunk length the
    kernel adds per-chunk contributions in the scan's association order,
    so round states and finals equal the group-by scan's
    (``gla.group_partials``) bit-for-bit on XLA:CPU
    (tests/test_groupby_kernel.py, docs/KERNELS.md §2/§6).
    """
    interpret = _interpret_default() if interpret is None else interpret
    if vals.ndim == 1:
        vals = vals[:, None]
    N, A = vals.shape
    A_pad = -(-A // 8) * 8
    G_pad = -(-num_groups // 128) * 128
    v = jnp.zeros((N, A_pad), jnp.float32).at[:, :A].set(vals.astype(jnp.float32))
    v = _pad_rows(v, block_rows)
    w = _pad_rows(weight.astype(jnp.float32)[:, None], block_rows)
    # padded rows get weight 0 AND an in-range gid so the one-hot is harmless
    g = _pad_rows(gids.astype(jnp.int32)[:, None], block_rows)
    sums, sumsqs, matched = _gk.group_agg_kernel(
        v, w, g, num_groups=G_pad, block_rows=block_rows, interpret=interpret
    )
    return (sums[:num_groups, :A], sumsqs[:num_groups, :A],
            matched[:num_groups, 0])


# re-export oracles for convenience
chunk_agg_ref = _ref.chunk_agg_ref
q6_agg_ref = _ref.q6_agg_ref
group_agg_ref = _ref.group_agg_ref
