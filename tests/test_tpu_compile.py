"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a topology
that is only described.  That refuses what Pallas interpret mode accepts:
block shapes off the (8, 128) tiling, scatters and 1-D gathers in a
kernel body, programs over the chip's 16 GiB.  Nothing here runs; each
test compiles one kernel or program at a real width in a second or two.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, gla
from repro.data import encodings as ENC
from repro.data import tpch
from repro.kernels import fused_agg as FK
from repro.kernels import ops

L = 1024
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _lineitem_shapes(lead, sharding):
    """Shape structs of the lineitem columns + ``_mask``, leading dims
    ``lead`` and L rows per chunk."""
    dtypes = {k: v.dtype for k, v in tpch.generate_lineitem(8).items()}
    dtypes["_mask"] = jnp.float32
    return {k: jax.ShapeDtypeStruct((*lead, L), d, sharding=sharding)
            for k, d in dtypes.items()}


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: tree))


def _q6():
    return gla.make_sum_gla(tpch.q6_func, tpch.q6_cond(tpch.Q6_LOW_WINDOW),
                            d_total=2.0**26)


def _q1_small():
    return gla.make_groupby_gla(tpch.q1_func, tpch.q1_cond,
                                tpch.q1_group_small, num_groups=4,
                                d_total=2.0**26, num_aggs=4)


@pytest.mark.parametrize("encoding", ["plain", "dict"])
@pytest.mark.parametrize("name", ["q6", "q1_small"])
def test_fused_round_step_compiles(one_chip, name, encoding):
    """The fused round step, scalar and group, at C=64 chunks of L=1024:
    compiled (not interpret), so group members take the one-hot MXU
    contraction and the program holds a Mosaic kernel.  The dict case
    ships ``discount`` as int8 codes, decoded in the kernel."""
    g = {"q6": _q6, "q1_small": _q1_small}[name]()
    cols = _lineitem_shapes((64,), one_chip)
    encs = ()
    if encoding == "dict":
        raw = tpch.generate_lineitem(4096)
        encs = ENC.normalize_encodings(
            {"discount": ENC.dict_encoding_for(raw["discount"])})
        cols["discount"] = jax.ShapeDtypeStruct((64, L), jnp.int8,
                                                sharding=one_chip)
    state = _on(g.init(), one_chip)
    compiled = jax.jit(
        lambda st, c: FK.fused_round_step(g, st, c, encs, interpret=False)
    ).lower(state, cols).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_prefix_states_compiles(one_chip):
    cols = _lineitem_shapes((64,), one_chip)
    g = _q6()
    compiled = jax.jit(
        lambda c: FK.fused_prefix_states(g, c, interpret=False)
    ).lower(cols).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_group_agg_compiles_large_domain(one_chip):
    """The legacy one-hot group kernel at q1-large's 2^13 buckets."""
    n = 64 * L
    compiled = ops.group_agg.lower(
        jax.ShapeDtypeStruct((n, 4), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        num_groups=8192, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["q6", "q1_small"])
def test_whole_scan_program_fits_one_chip(one_chip, name):
    """The default whole-scan program (emit="chunk") at P=8 × C=8192 ×
    L=1024 — 2^26 rows, about TPC-H SF-11 — compiles, and its arguments,
    outputs and temporaries fit one chip's HBM."""
    g = {"q6": _q6, "q1_small": _q1_small}[name]()
    P, C, R = 8, 8192, 8
    shards = _lineitem_shapes((P, C), one_chip)
    sched = jax.ShapeDtypeStruct((P, R + 1), jnp.int32, sharding=one_chip)
    alive = jax.ShapeDtypeStruct((P,), jnp.bool_, sharding=one_chip)
    compiled = engine._run_vmapped.lower(
        g, shards, sched, alive, mode="async", emit="chunk", lanes=1,
        snapshots=True, confidence=0.95, all_alive=True).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
