"""Distributed randomization (paper §4.2): dtype preservation through the
shuffle, including partitions that receive no rows (empty buckets)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import randomize


def _tiny_parts():
    """Two tiny partitions with int32 + float32 columns."""
    return [
        {"shipdate": jnp.arange(3, dtype=jnp.int32),
         "extendedprice": jnp.asarray([1.5, 2.5, 3.5], jnp.float32)},
        {"shipdate": jnp.arange(4, dtype=jnp.int32),
         "extendedprice": jnp.asarray([4.5, 5.5, 6.5, 7.5], jnp.float32)},
    ]


def test_empty_bucket_preserves_dtype():
    """A partition that receives no rows in the shuffle must keep the source
    dtypes — the old np.zeros((0,)) fallback promoted int32 to float64."""
    # key(27) routes every row away from one partition at this tiny size
    # under jax 0.9's PRNG
    # (deterministic; asserted below so a jax PRNG change cannot silently
    # turn this into a non-regression test)
    out = randomize.randomize_distributed(_tiny_parts(), jax.random.key(27))
    sizes = [o["shipdate"].shape[0] for o in out]
    assert 0 in sizes, f"shuffle no longer produces an empty bucket: {sizes}"
    for o in out:
        assert o["shipdate"].dtype == jnp.int32
        assert o["extendedprice"].dtype == jnp.float32
    assert sum(sizes) == 7  # nothing lost


def test_zero_row_source_partition_preserves_dtype():
    parts = [
        {"shipdate": jnp.zeros((0,), jnp.int32)},
        {"shipdate": jnp.arange(4, dtype=jnp.int32)},
    ]
    out = randomize.randomize_distributed(parts, jax.random.key(0))
    for o in out:
        assert o["shipdate"].dtype == jnp.int32
    assert sum(o["shipdate"].shape[0] for o in out) == 4


def test_empty_bucket_packs_into_engine_layout():
    """pack_partitions keeps the int32 columns int32 even when one partition
    is empty, so group ids stay integral downstream."""
    out = randomize.randomize_distributed(_tiny_parts(), jax.random.key(27))
    shards = randomize.pack_partitions(out, chunk_len=4)
    assert shards["shipdate"].dtype == jnp.int32
    assert shards["extendedprice"].dtype == jnp.float32
    # empty partition contributes only masked padding
    dead = int(np.argmin(np.asarray(shards["_mask"]).sum(axis=(1, 2))))
    assert np.asarray(shards["_mask"])[dead].sum() == 0
