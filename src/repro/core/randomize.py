"""Data randomization — paper §4.2.

On-line aggregation needs samples; PF-OLA's choice (shared with DBO/CONTROL)
is to store data in random order so a *sequential scan* yields a
without-replacement sample prefix.  The single-estimator model additionally
needs **global** randomization: any prefix of any union of partition scans
must be a uniform sample of the whole dataset.

Two implementations:

  * :func:`randomize_global` — reference: one global permutation, then split
    into partitions.  Used as the statistical oracle in tests.
  * :func:`randomize_distributed` — the paper's two-stage parallel algorithm:
    (1) each partition assigns every local item an independent uniform target
    partition (random hash on a per-item random value — NOT on item content),
    then items are exchanged (the all-to-all "shuffle"); (2) each partition
    sorts its received items by fresh per-item random keys (a local random
    permutation), which "separates items received from the same origin".

Both operate on columnar dicts.  The distributed variant keeps per-partition
cardinalities ragged (as in a real shuffle); :func:`pack_partitions` pads to a
rectangular [P, n_max] layout with a ``_mask`` column, which is what the
engine consumes.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

Columns = Dict[str, jnp.ndarray]


def randomize_global(cols: Columns, key, num_partitions: int) -> List[Columns]:
    """Reference: global permutation, then round-robin split into partitions.

    Device columns are permuted on their device.  Host (NumPy) columns
    stay on the host: only the permutation is drawn on the default device,
    and the gather and split run in NumPy — so a load for a mesh never
    holds the whole dataset on one device.
    """
    n = next(iter(cols.values())).shape[0]
    perm = jax.random.permutation(key, n)
    if all(isinstance(v, np.ndarray) for v in cols.values()):
        perm = np.asarray(perm)
    shuffled = {k: v[perm] for k, v in cols.items()}
    # Contiguous split (equal sizes up to remainder).
    bounds = np.linspace(0, n, num_partitions + 1).astype(int)
    return [
        {k: v[bounds[i]:bounds[i + 1]] for k, v in shuffled.items()}
        for i in range(num_partitions)
    ]


def randomize_distributed(
    parts: List[Columns], key, num_partitions: int | None = None
) -> List[Columns]:
    """Paper §4.2 two-stage algorithm over already-partitioned data.

    Stage 1: for each local item draw an independent uniform target partition
    (the "random hash of a random value"); exchange.  Stage 2: per-partition
    random permutation via sort on fresh random keys.  Runs on host numpy —
    this is the *load-time* path (the paper folds it into data loading).
    """
    num_partitions = num_partitions or len(parts)
    keys = jax.random.split(key, 2 * len(parts) + num_partitions)
    # Stage 1: draw targets and scatter.
    buckets: List[Dict[str, list]] = [
        {k: [] for k in parts[0]} for _ in range(num_partitions)
    ]
    for i, p in enumerate(parts):
        n_i = next(iter(p.values())).shape[0]
        tgt = np.asarray(jax.random.randint(keys[i], (n_i,), 0, num_partitions))
        for k, v in p.items():
            v = np.asarray(v)
            for j in range(num_partitions):
                buckets[j][k].append(v[tgt == j])
    out: List[Columns] = []
    dtypes = {k: np.asarray(v).dtype for k, v in parts[0].items()}
    for j in range(num_partitions):
        # Empty buckets (a partition that received no rows) must keep the
        # source dtype: a bare np.zeros((0,)) would silently promote int32
        # columns (shipdate, rfls, suppkey) to float64 downstream.
        cat = {k: (np.concatenate(vs) if vs
                   else np.zeros((0,), dtypes[k])).astype(dtypes[k],
                                                          copy=False)
               for k, vs in buckets[j].items()}
        n_j = next(iter(cat.values())).shape[0]
        # Stage 2: fresh random keys -> sort = local random permutation.
        # (Reusing origin-node random values is NOT valid — paper §4.2.)
        rk = np.asarray(jax.random.uniform(keys[len(parts) + j], (n_j,)))
        order = np.argsort(rk)
        out.append({k: jnp.asarray(v[order]) for k, v in cat.items()})
    return out


def pack_partitions(
    parts: List[Columns], chunk_len: int, *, min_chunks: int | None = None,
    sharding=None,
) -> Columns:
    """Pad ragged partitions to [P, C, L] chunked columns with a _mask.

    The engine consumes this layout.  ``_mask`` marks live items; padded
    slots never contribute to any GLA state (uda.Chunk contract).

    The columns are packed on the host and then placed: on the default
    device, or by ``sharding`` — with partitions on a mesh axis
    (``NamedSharding(mesh, PartitionSpec("data"))``) each device receives
    only its own partitions, straight from the host buffer.
    """
    def put(buf):
        return jnp.asarray(buf) if sharding is None else jax.device_put(
            buf, sharding)

    P = len(parts)
    ns = [next(iter(p.values())).shape[0] for p in parts]
    C = max(-(-n // chunk_len) for n in ns)  # ceil
    if min_chunks is not None:
        C = max(C, min_chunks)
    total = C * chunk_len
    out: Dict[str, np.ndarray] = {}
    names = list(parts[0].keys())
    for k in names:
        buf = np.zeros((P, total), dtype=np.asarray(parts[0][k]).dtype)
        for i, p in enumerate(parts):
            v = np.asarray(p[k])
            buf[i, : v.shape[0]] = v
        out[k] = put(buf.reshape(P, C, chunk_len))
    mask = np.zeros((P, total), dtype=np.float32)
    for i, n in enumerate(ns):
        mask[i, :n] = 1.0
    out["_mask"] = put(mask.reshape(P, C, chunk_len))
    return out
