"""Fused selection→bucket→aggregate kernel throughput (DESIGN.md §12).

The raw-speed certificate for the fused Pallas path: q6-class selective
scans and the q1 group-by, fused-kernel dispatch vs the segment-sum scan
path, on plain and encoded sources.  Wins are reported as
**fraction-of-roofline**, not just speedups: each row derives

    achieved_gbps     = bytes the scan must move / wall time
    roofline_fraction = achieved_gbps / the chip's HBM bandwidth
                        (repro.launch.mesh.chip_peaks, keyed by the
                        device kind) — TPU runs only; elsewhere it is
                        None, "not measured"

and encoded sources score their *physical* bytes — the stream the
dictionary / bit-packed columns actually move — so the decode-in-kernel
bandwidth win shows up as the SAME aggregate answer from fewer bytes.
That byte shrinkage is not read off trustingly: the audit catalog's
``bytes_moved`` check is run with ``raise_on_failure=True`` before
timing, and every fused/encoded result is checked against the plain
scan-path result: bitwise where the kernel shares the scan's expression
tree, within the float64-oracle tolerance where it does not.

Output: CSV to stdout + benchmarks/out/BENCH_fused.json (schema rows in
benchmarks/README.md; seeded baseline in benchmarks/baselines/).
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks import bench_io
except ImportError:  # direct script invocation: benchmarks/ is sys.path[0]
    import bench_io

from repro.analysis import audit as AU
from repro.core import engine, gla, randomize
from repro.core import session as S
from repro.core.spec import QuerySpec
from repro.data import encodings as ENC
from repro.data import source as DS
from repro.data import tpch
from repro.launch.mesh import chip_peaks

ROWS = 2_000_000
SMOKE_ROWS = 400_000
PARTS = 4
CHUNK = 1024
ROUNDS = 16


def _shards(rows):
    cols = tpch.generate_lineitem(rows, seed=29)
    parts = randomize.randomize_global(
        {k: jnp.asarray(v) for k, v in cols.items()}, jax.random.key(29),
        PARTS)
    n_chunks = -(-rows // PARTS // CHUNK)
    return randomize.pack_partitions(
        parts, chunk_len=CHUNK,
        min_chunks=-(-n_chunks // ROUNDS) * ROUNDS)


def _wide_q6(d_total):
    """q6-class selective SUM over a dense (~80%) shipdate window."""
    def func(c):
        return c["quantity"]

    def cond(c):
        sd = c["shipdate"]
        return ((sd >= 0) & (sd < 1460)).astype(jnp.float32)

    return gla.make_sum_gla(func, cond, d_total=d_total)


def _families(rows):
    d = float(rows)
    return {
        "q6_sum": _wide_q6(d),
        "q1_groupby": gla.make_groupby_gla(
            tpch.q1_func, tpch.q1_cond, tpch.q1_group_small, num_groups=4,
            d_total=d, num_aggs=4),
    }


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
               for v in jax.tree.leaves(tree))


def _roofline(bytes_moved: int, us: float) -> dict:
    gbps = bytes_moved / (us / 1e6) / 1e9
    dev = jax.devices()[0]
    frac = (gbps / (chip_peaks(dev.device_kind).hbm_bw / 1e9)
            if dev.platform == "tpu" else None)
    return {"bytes_moved": bytes_moved,
            "achieved_gbps": gbps,
            "roofline_fraction": frac}


def run(rows=ROWS, repeats=3, out=sys.stdout):
    shards = _shards(rows)
    np_shards = {k: np.asarray(v) for k, v in shards.items()}
    spec = DS.InMemorySource(shards).spec
    logical_bytes = _tree_bytes(spec.slice_like(spec.C))

    esrc = DS.EncodedSource.from_shards(np_shards, {
        "discount": ENC.dict_encoding_for(np_shards["discount"]),
        "shipdate": ENC.BitPackedEncoding(bits=16),
        "rfls": ENC.BitPackedEncoding(bits=2)})
    physical_bytes = _tree_bytes(
        {k: v for k, v in zip(sorted(np_shards),
                              jax.tree.leaves(esrc.step_slice_like(spec.C)))})

    bench_rows = []
    print("name,us_per_call,derived", file=out)

    for fam, q in _families(rows).items():
        # pre-timing certificates: the fused plan really is one dispatch,
        # and the encoded stream really is smaller (audit catalog)
        AU.audit_plan(q, shards, rounds=ROUNDS, emit="kernel",
                      checks=("fused_single_dispatch",),
                      raise_on_failure=True)
        enc_report = AU.audit_plan(
            q, esrc, rounds=ROUNDS, emit="kernel",
            checks=("fused_single_dispatch", "bytes_moved"),
            raise_on_failure=True)
        byte_ratio = enc_report.result("bytes_moved").data["ratio"]

        def run_scan(q=q):
            res = engine.run_query(QuerySpec(q, rounds=ROUNDS, emit="chunk"),
                                   shards)
            jax.block_until_ready(res.final)
            return res

        def run_fused(q=q):
            res = engine.run_query(QuerySpec(q, rounds=ROUNDS,
                                             emit="kernel"), shards)
            jax.block_until_ready(res.final)
            return res

        def run_encoded(q=q):
            sess = S.Session(QuerySpec(q, rounds=ROUNDS, emit="kernel"),
                             esrc)
            while not sess.done:
                sess.step()
            res = sess.result()
            jax.block_until_ready(res.final)
            return res

        scan_us, fused_us, enc_us = bench_io.time_interleaved(
            [run_scan, run_fused, run_encoded], repeats)

        # the whole point of bitwise finals: speed claims are apples to
        # apples — same answer, fewer seconds / fewer bytes
        ref = run_scan()
        tol = 2 * tpch.f32_sum_tolerance(int(shards["_mask"].size))
        bitwise = {}
        for label, res in (("fused", run_fused()),
                           ("encoded", run_encoded())):
            pairs = [(np.asarray(a), np.asarray(b)) for a, b in zip(
                jax.tree.leaves(res.final), jax.tree.leaves(ref.final))]
            bitwise[label] = all(a.tobytes() == b.tobytes() for a, b in pairs)
            for a, b in pairs:
                assert np.all(np.abs(a - b) <= tol * np.abs(b)), (
                    f"{label} {fam} final is outside the f32 tolerance of "
                    "the scan path")

        rows_out = [
            ("scan_" + fam, scan_us, {
                "rows": rows, "rounds": ROUNDS,
                **_roofline(logical_bytes, scan_us)}),
            ("fused_" + fam, fused_us, {
                "rows": rows, "rounds": ROUNDS,
                "speedup_vs_scan": scan_us / fused_us,
                "bitwise_vs_scan": bitwise["fused"],
                **_roofline(logical_bytes, fused_us)}),
            ("encoded_fused_" + fam, enc_us, {
                "rows": rows, "rounds": ROUNDS,
                "byte_ratio_vs_logical": byte_ratio,
                "logical_bytes": logical_bytes,
                "bitwise_vs_scan": bitwise["encoded"],
                **_roofline(physical_bytes, enc_us)}),
        ]
        for name, us, derived in rows_out:
            frac = derived["roofline_fraction"]
            frac = "not measured" if frac is None else f"{frac:.4f}"
            print(f"{name},{us:.0f},roofline_frac={frac}", file=out)
            bench_rows.append({"name": name, "us_per_call": us,
                               "derived": derived})

    path = bench_io.emit("fused", bench_rows)
    print(f"# wrote {path}", file=out)


if __name__ == "__main__":
    run(rows=int(sys.argv[1]) if len(sys.argv) > 1 else ROWS)
