"""Fused selection→bucket→aggregate Pallas kernel (DESIGN.md §12).

One VMEM-resident dispatch per round-slice that fuses everything between
the streamed bytes and the GLA state update:

    decode (dict / bit-packed columns)          repro/data/encodings.py
    → predicate evaluation  (FusedSpec.cond × _mask)
    → group-id computation  (FusedSpec.group, already hash-bucketed)
    → f32 accumulation      (mul-reduce scalar / scatter or one-hot group)

into the ``estimators.SumState`` layout, *carrying the state in*: the
previous round's (sum, sumsq, matched) enter as constant-index-map input
refs, are copied to the output refs at ``program_id == 0``, and each grid
step accumulates one tile-aligned block of chunks on top, chunk by chunk
in chunk order — the association ``scan.scan_round_step`` uses.

Blocking (docs/KERNELS.md, TPU lowering rules): a grid step reads a
``[BLOCK_CHUNKS, L]`` block of every ``[C, L]`` column (or the whole
chunk axis when C is shorter), because a TPU block's second-minor dim must
be a multiple of 8 or the whole array dim.  A loop inside the step folds
the block's chunks one row at a time; a partial last block stops at C.

Equality (docs/KERNELS.md rule 2):

  * scalar members repeat ``gla.acc_sum``'s expression tree per chunk, so
    interpret-mode (CPU) states are bitwise-identical to the scan path;
  * group members scatter with ``jax.ops.segment_sum`` in interpret mode
    and with a one-hot MXU contraction in compiled TPU kernels, where
    scatter does not lower.  The scan path takes the HIGHEST one-hot dot
    up to ``gla.ONEHOT_MAX_GROUPS`` groups, ``segment_sum`` above; on
    XLA:CPU the two agree bitwise at chunk lengths up to 256.  On TPU the
    kernel's contract is the written float64-oracle tolerance, not
    bitwise.

Bundles fuse further: all members' accumulations run in the SAME
``pallas_call`` (separate out-ref triples per member), so N concurrent
queries still cost one dispatch and one VMEM residency per round-slice —
the audit catalog's ``fused_single_dispatch`` check pins this down via
:func:`count_dispatches`.

Padding follows the repo's MXU discipline (docs/KERNELS.md): A → multiple
of 8, G → multiple of 128; padded value columns are zero (they reduce to
zero independently per column), padded group rows receive no one-hot hits,
and the unpadded slices are returned — padding never leaks.

Kernels run with ``interpret=True`` off-TPU (ops._interpret_default).
Plans with no TPU lowering — join probe lookups and bit-packed decode,
both of which need a gather or a lane-moving reshape — raise
:class:`FusedLoweringError` when a compiled kernel is built.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.data import encodings as ENC
from repro.kernels.ops import _interpret_default

# Chunks per grid step: one f32 sublane tile.  A TPU block's second-minor
# dim must be a multiple of 8 or equal the whole array dim.
BLOCK_CHUNKS = 8


class FusedLoweringError(ValueError):
    """The plan's fused kernel has no compiled TPU lowering.

    Raised while the kernel is built, so the engine or session call that
    asked for ``emit="kernel"`` fails by name instead of falling back.
    """


def _pad8(a: int) -> int:
    return -(-a // 8) * 8


def _pad128(g: int) -> int:
    return -(-g // 128) * 128


# Join probe tables ride inside the kernel's VMEM residency for the whole
# grid (constant index map — fetched once, revisited every step), so their
# combined footprint is budgeted against the ~16 MiB/core VMEM the column
# blocks and accumulators also live in.  Oversized joins fall back to the
# legacy kernel_cols path (fused_available returns False).
PROBE_VMEM_BUDGET_BYTES = 4 * 1024 * 1024


# ---------------------------------------------------------------------------
# dispatch accounting (analysis/audit.py: fused_single_dispatch)
# ---------------------------------------------------------------------------

_DISPATCHES = [0]  # pallas_call constructions since import (monotonic)


@contextlib.contextmanager
def count_dispatches():
    """Count fused ``pallas_call`` constructions traced inside the block.

    Yields a one-element list; after the block it holds the count.  Works
    under ``jax.eval_shape``/lowering (no execution needed), which is how
    the audit catalog proves one-dispatch-per-round-slice statically.
    """
    start = _DISPATCHES[0]
    box = [0]
    try:
        yield box
    finally:
        box[0] = _DISPATCHES[0] - start


# ---------------------------------------------------------------------------
# contract helpers
# ---------------------------------------------------------------------------

def fused_members(gla):
    """The per-member ``FusedSpec`` tuple of ``gla`` (itself, or its bundle
    members), or None when any member lacks a fused contract."""
    members = gla.members or (gla,)
    specs = tuple(m.fused for m in members)
    return None if any(s is None for s in specs) else specs


def unique_probes(specs):
    """Unique ProbeTables across member specs, first-seen order (members
    built from one ``with_probe_tables`` join share table objects — shared
    tables enter the kernel once)."""
    seen = {}
    for fs in specs:
        for pt in fs.probe_tables:
            seen.setdefault(pt.key, pt)
    return tuple(seen.values())


def probe_bytes(gla) -> int:
    """Combined unique probe-table bytes of ``gla``'s fused contract (0 when
    none) — the number ``fused_available`` holds under the VMEM budget."""
    specs = fused_members(gla)
    return 0 if specs is None else sum(
        pt.nbytes for pt in unique_probes(specs))


def fused_available(gla, columns=None) -> bool:
    """True when every member publishes a fused contract AND the source's
    column table is fusable (no trailing dims — the kernel blocks
    ``[BLOCK_CHUNKS, L]`` rows per column) AND any join probe tables fit
    the kernel's VMEM probe budget."""
    specs = fused_members(gla)
    if specs is None:
        return False
    if columns is not None and any(c.trailing for c in columns):
        return False
    probes = unique_probes(specs)
    if sum(pt.nbytes for pt in probes) > PROBE_VMEM_BUDGET_BYTES:
        return False
    return True


def _check_lowerable(gla, specs, enc_map, interpret: bool) -> None:
    """Refuse, by name, a compiled kernel the TPU compiler cannot build."""
    if interpret:
        return
    if unique_probes(specs):
        raise FusedLoweringError(
            f"GLA {gla.name!r}: join probe lookups are gathers, which do not "
            "lower in a compiled TPU kernel — run this plan with "
            "emit='chunk' or emit='round'")
    packed = sorted(n for n, e in enc_map.items()
                    if isinstance(e, ENC.BitPackedEncoding))
    if packed:
        raise FusedLoweringError(
            f"bit-packed columns {packed}: the in-kernel unpack reshapes "
            "words across lanes, which a compiled TPU kernel does not "
            "lower — scan them with emit='chunk' or emit='round'")


def _member_meta(specs):
    """Static (kind, A, A_pad, G, G_pad) per member."""
    meta = []
    for fs in specs:
        a_pad = _pad8(fs.num_aggs)
        if fs.group is None:
            meta.append(("scalar", fs.num_aggs, a_pad, None, None))
        else:
            meta.append(("group", fs.num_aggs, a_pad, fs.num_groups,
                         _pad128(fs.num_groups)))
    return meta


def _pad_cols(d, a_pad):
    """Zero-pad a [rows, A] contribution to [rows, A_pad] columns."""
    if d.shape[1] == a_pad:
        return d
    return jnp.concatenate(
        [d, jnp.zeros((d.shape[0], a_pad - d.shape[1]), jnp.float32)], axis=1)


def _pad_rows(d, g_pad):
    """Zero-pad a [G, cols] contribution to [G_pad, cols] rows."""
    if d.shape[0] == g_pad:
        return d
    return jnp.concatenate(
        [d, jnp.zeros((g_pad - d.shape[0], d.shape[1]), jnp.float32)], axis=0)


def _chunk_contrib(fs, meta_row, chunk, msk, L, use_mxu):
    """One chunk's (sum, sumsq, matched) contribution, padded.

    The scalar member repeats ``gla.acc_sum`` verbatim (multiply-then-
    reduce — context-stable on XLA:CPU, unlike a matvec, which fuses into
    surrounding scan carries), so its states are bitwise-identical to the
    scan path on every backend that shares the expression tree.

    Group members scatter with ``jax.ops.segment_sum`` unless ``use_mxu``,
    which takes the one-hot MXU contraction instead — the compiled TPU
    lowering, where a scatter does not lower.  The scan path's
    ``gla.group_partials`` takes the same HIGHEST dot up to
    ``gla.ONEHOT_MAX_GROUPS`` groups; on XLA:CPU the scatter and the dot
    agree bitwise at L ≤ 256.  A compiled kernel's group members are held
    to the float64-oracle tolerance (docs/KERNELS.md rule 2).

    Reductions run over the UNPADDED [L, A] values / [G, A] segments —
    padding A (or G) first changes the reduce's vectorization, hence its
    association, hence the low bits; only the already-reduced result is
    padded to the accumulator-ref layout.  Returns 2-D arrays shaped like
    the member's accumulator refs.
    """
    kind, A, A_pad, G, G_pad = meta_row
    vals = fs.func(chunk)
    vals = (vals[:, None] if vals.ndim == 1 else vals).astype(jnp.float32)
    w = (fs.cond(chunk) * msk).astype(jnp.float32)
    if kind == "scalar":
        d_s = ((vals * w[:, None]).sum(axis=0))[None]            # [1, A]
        d_q = (((vals * vals) * w[:, None]).sum(axis=0))[None]
        d_m = jnp.sum(w).reshape(1, 1)
        return _pad_cols(d_s, A_pad), _pad_cols(d_q, A_pad), d_m
    gids = fs.group(chunk).astype(jnp.int32)
    vw = vals * w[:, None]
    if use_mxu:
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (L, G_pad), 1)
                  == gids[:, None]).astype(jnp.float32)          # [L, G_pad]
        # f32 passes: a default-precision MXU dot rounds vw to bf16
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.HIGHEST)
        d_s = dot(onehot.T, vw)
        d_q = dot(onehot.T, vals * vw)
        d_m = dot(onehot.T, w[:, None])
        return _pad_cols(d_s, A_pad), _pad_cols(d_q, A_pad), d_m
    d_s = jax.ops.segment_sum(vw, gids, num_segments=G)
    d_q = jax.ops.segment_sum(vals * vw, gids, num_segments=G)
    d_m = jax.ops.segment_sum(w, gids, num_segments=G)[:, None]
    return (_pad_rows(_pad_cols(d_s, A_pad), G_pad),
            _pad_rows(_pad_cols(d_q, A_pad), G_pad),
            _pad_rows(d_m, G_pad))


def _dict_select(codes, enc):
    """Dictionary decode as a compare-select chain over the value table.

    The table's values are static (``DictEncoding.values``), so they enter
    the body as literals — no table operand, and no gather (a 1-D gather
    does not lower in a compiled TPU kernel).  Each element is selected,
    never computed, so the result is the table's exact bit pattern, as a
    gather's would be.
    """
    codes = codes.astype(jnp.int32)
    values = np.asarray(enc.values, dtype=enc.logical_dtype)
    out = jnp.zeros(codes.shape, values.dtype)
    for k, v in enumerate(values):
        out = jnp.where(codes == k, v, out)
    return out


def _row(ref, j):
    """Row ``j`` of a ``[B, width]`` chunk block.

    A dynamic sublane index into a packed (narrower than 32-bit) block
    does not lower in a compiled TPU kernel, and neither does a
    dynamic_slice of a loaded value.  Such blocks (dict codes) widen to
    int32 and pick the row with a one-hot select-and-sum, which is exact:
    one term is nonzero.
    """
    if jnp.dtype(ref.dtype).itemsize >= 4:
        return ref[j]
    blk = ref[...].astype(jnp.int32)
    pick = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == j
    return jnp.sum(jnp.where(pick, blk, 0), axis=0).astype(ref.dtype)


def _decode_chunk(names, col_refs, j, enc_map):
    """Rebuild the logical chunk dict from row ``j`` of one grid step's
    column blocks, decoding encoded columns in-register (exact)."""
    chunk = {}
    for n, r in zip(names, col_refs):
        enc = enc_map.get(n)
        if isinstance(enc, ENC.DictEncoding):
            chunk[n] = _dict_select(_row(r, j), enc)
        else:
            chunk[n] = ENC.decode_block(_row(r, j), enc)
    return chunk


def _for_chunks(step, i, B, C):
    """Run ``step(j)`` over the rows of grid step ``i``'s block in chunk
    order, one chunk per loop iteration (a loop, not an unrolled block, so
    each chunk's work traces alone — the context the scan's own chunk body
    has).  A partial last block's rows past C hold undefined data and are
    never visited."""
    def body(j, carry):
        step(j)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(B, C - i * B), body, 0)


def _carry_arrays(specs, meta, states):
    """Pack member SumStates into the padded f32 carry layout."""
    carries = []
    for fs, mrow, st in zip(specs, meta, states):
        kind, A, A_pad, G, G_pad = mrow
        if kind == "scalar":
            s = jnp.zeros((1, A_pad), jnp.float32).at[0, :A].set(st.sum)
            q = jnp.zeros((1, A_pad), jnp.float32).at[0, :A].set(st.sumsq)
            m = jnp.asarray(st.matched, jnp.float32).reshape(1, 1)
        else:
            s = jnp.zeros((G_pad, A_pad), jnp.float32).at[:G, :A].set(st.sum)
            q = jnp.zeros((G_pad, A_pad), jnp.float32).at[:G, :A].set(st.sumsq)
            m = jnp.zeros((G_pad, 1), jnp.float32).at[:G, 0].set(st.matched)
        carries += [s, q, m]
    return carries


def _unpack_states(outs, specs, meta, states, scanned_delta):
    """Slice padding off the kernel outputs back into member SumStates."""
    new_states = []
    for i, (mrow, st) in enumerate(zip(meta, states)):
        kind, A, A_pad, G, G_pad = mrow
        s, q, m = outs[3 * i:3 * i + 3]
        if kind == "scalar":
            new_states.append(st._replace(
                sum=s[0, :A], sumsq=q[0, :A], matched=m[0, 0],
                scanned=st.scanned + scanned_delta))
        else:
            new_states.append(st._replace(
                sum=s[:G, :A], sumsq=q[:G, :A], matched=m[:G, 0],
                scanned=st.scanned + scanned_delta))
    return new_states


def _column_operands(cols, names, B):
    """Column arrays and their ``[B, width]`` chunk-block specs."""
    args = [cols[n] for n in names]
    specs = [pl.BlockSpec((B, int(cols[n].shape[1])), lambda i: (i, 0))
             for n in names]
    return args, specs


def _probe_operands(probes):
    """Probe-table arrays, each one whole-array block for the grid."""
    args = [jnp.asarray(pt.values) for pt in probes]
    specs = [pl.BlockSpec(a.shape, lambda i, _nd=a.ndim: (0,) * _nd)
             for a in args]
    return args, specs


# ---------------------------------------------------------------------------
# the fused round-step kernel (carry-in; scalar, group, and bundles)
# ---------------------------------------------------------------------------

def fused_round_step(gla, state, cols, encodings=(), *, interpret=None,
                     use_mxu=None):
    """Advance ``state`` over one round-slice in ONE fused dispatch.

    Contract (docs/KERNELS.md):
      cols:       {name: [C, L]} logical — or [C, L/lanes] physical for
                  columns named in ``encodings`` (decoded in-kernel);
                  must include a plain ``_mask``.
      state:      member SumState (bundle: tuple thereof), any f32 shapes
                  matching the GLA's init().
      returns:    same pytree, advanced over the C chunks in chunk order.

    Join members' ``FusedSpec.probe_tables`` enter as extra whole-array
    operands (constant index map — one VMEM residency for the grid) and are
    injected into the in-kernel chunk dict under their keys before the
    member closures run, so the in-kernel gather repeats the scan path's
    expression tree verbatim (interpret mode only: see
    :class:`FusedLoweringError`).

    ``use_mxu`` picks the group members' accumulation: the one-hot MXU
    contraction (True) or the segment_sum scatter (False).  It
    defaults to the compile target — one-hot in a compiled kernel, where
    scatter does not lower, scatter under interpret mode.

    Equality: with the scatter, identical on XLA:CPU (chunk lengths up to
    256) to folding ``gla.accumulate`` over the C chunks
    (``scan.scan_round_step``), including from a checkpointed mid-scan
    carry; scalar members are identical either way.
    ``scanned`` (and nothing else) is accumulated outside the kernel —
    live counts are integer-valued f32, exact under any association, and
    need only ``_mask``.
    """
    interpret = _interpret_default() if interpret is None else interpret
    use_mxu = not interpret if use_mxu is None else use_mxu
    specs = fused_members(gla)
    if specs is None:
        raise ValueError(
            f"GLA {gla.name!r} does not publish a fused kernel contract")
    probes = unique_probes(specs)
    pbytes = sum(pt.nbytes for pt in probes)
    if pbytes > PROBE_VMEM_BUDGET_BYTES:
        raise ValueError(
            f"GLA {gla.name!r}: probe tables need {pbytes} bytes, over the "
            f"{PROBE_VMEM_BUDGET_BYTES}-byte kernel VMEM budget — route "
            f"this plan through the legacy kernel_cols path")
    enc_map = dict(encodings)
    _check_lowerable(gla, specs, enc_map, interpret)
    is_bundle = bool(gla.members)
    states = tuple(state) if is_bundle else (state,)
    meta = _member_meta(specs)
    names = sorted(cols)
    mask = cols["_mask"]
    C, L = int(mask.shape[0]), int(mask.shape[1])
    B = min(C, BLOCK_CHUNKS)

    carries = _carry_arrays(specs, meta, states)
    col_args, col_specs = _column_operands(cols, names, B)
    probe_args, probe_specs = _probe_operands(probes)
    carry_specs = [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in carries]
    out_shape = [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carries]
    n_cols, n_probe, n_carry = len(names), len(probes), len(carries)

    def body(*refs):
        col_refs = refs[:n_cols]
        probe_refs = refs[n_cols:n_cols + n_probe]
        in_refs = refs[n_cols + n_probe:n_cols + n_probe + n_carry]
        out_refs = refs[n_cols + n_probe + n_carry:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _seed():
            for o, c in zip(out_refs, in_refs):
                o[...] = c[...]

        probe_vals = {pt.key: r[...] for pt, r in zip(probes, probe_refs)}

        def chunk_step(j):
            chunk = _decode_chunk(names, col_refs, j, enc_map)
            chunk.update(probe_vals)
            msk = chunk["_mask"].astype(jnp.float32)
            for k, (fs, mrow) in enumerate(zip(specs, meta)):
                d_s, d_q, d_m = _chunk_contrib(fs, mrow, chunk, msk, L,
                                               use_mxu)
                out_refs[3 * k][...] = out_refs[3 * k][...] + d_s
                out_refs[3 * k + 1][...] = out_refs[3 * k + 1][...] + d_q
                out_refs[3 * k + 2][...] = out_refs[3 * k + 2][...] + d_m

        _for_chunks(chunk_step, i, B, C)

    _DISPATCHES[0] += 1
    outs = pl.pallas_call(
        body, grid=(pl.cdiv(C, B),),
        in_specs=[*col_specs, *probe_specs, *carry_specs],
        out_specs=[pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in carries],
        out_shape=out_shape, interpret=interpret,
    )(*col_args, *probe_args, *carries)

    scanned_delta = jnp.sum(mask.astype(jnp.float32))
    new_states = _unpack_states(outs, specs, meta, states, scanned_delta)
    return tuple(new_states) if is_bundle else new_states[0]


# ---------------------------------------------------------------------------
# prefix-states kernel (scalar contract; per-chunk running states)
# ---------------------------------------------------------------------------

def fused_prefix_states(gla, cols, encodings=(), *, interpret=None):
    """Whole-shard scalar scan in ONE dispatch, emitting per-chunk prefixes.

    Contract: scalar (non-group, non-bundle) fused GLAs only.  Returns
    ``(final_state, prefix_states)`` where ``prefix_states`` leaves have a
    leading [C + 1] axis — row 0 is init(), row c+1 the state after chunk
    c — exactly the ``scan.scan_prefix`` layout the engines index round
    boundaries (and the sharded sync barrier's pmin truncation) from.

    The kernel keeps the running (sum, sumsq, matched) in revisited
    constant-index-map refs — sequential chunk-order adds, same
    association as the carry-in round step — and snapshots them into the
    chunk's row of a ``[B, A_pad]`` prefix block after each chunk, so the
    whole prefix family costs one dispatch (audit: single_kernel_dispatch
    counts 1 grid loop).  Bitwise-identical to folding ``gla.accumulate``
    chunk by chunk.
    """
    interpret = _interpret_default() if interpret is None else interpret
    specs = fused_members(gla)
    if specs is None or len(specs) != 1 or specs[0].group is not None:
        raise ValueError(
            f"fused_prefix_states needs a solo scalar fused GLA, got "
            f"{gla.name!r}")
    fs = specs[0]
    (meta_row,) = _member_meta((fs,))
    _, A, A_pad, _, _ = meta_row
    enc_map = dict(encodings)
    _check_lowerable(gla, specs, enc_map, interpret)
    names = sorted(cols)
    mask = cols["_mask"]
    C, L = int(mask.shape[0]), int(mask.shape[1])
    B = min(C, BLOCK_CHUNKS)

    probes = unique_probes((fs,))
    col_args, col_specs = _column_operands(cols, names, B)
    probe_args, probe_specs = _probe_operands(probes)
    acc_shapes = [jax.ShapeDtypeStruct((1, A_pad), jnp.float32),
                  jax.ShapeDtypeStruct((1, A_pad), jnp.float32),
                  jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    row_shapes = [jax.ShapeDtypeStruct((C, A_pad), jnp.float32),
                  jax.ShapeDtypeStruct((C, A_pad), jnp.float32),
                  jax.ShapeDtypeStruct((C, 1), jnp.float32)]
    acc_specs = [pl.BlockSpec(s.shape, lambda i: (0, 0)) for s in acc_shapes]
    row_specs = [pl.BlockSpec((B, s.shape[1]), lambda i: (i, 0))
                 for s in row_shapes]
    n_cols, n_probe = len(names), len(probes)

    def body(*refs):
        col_refs = refs[:n_cols]
        probe_refs = refs[n_cols:n_cols + n_probe]
        a_s, a_q, a_m, p_s, p_q, p_m = refs[n_cols + n_probe:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _seed():
            a_s[...] = jnp.zeros_like(a_s)
            a_q[...] = jnp.zeros_like(a_q)
            a_m[...] = jnp.zeros_like(a_m)

        probe_vals = {pt.key: r[...] for pt, r in zip(probes, probe_refs)}

        def chunk_step(j):
            chunk = _decode_chunk(names, col_refs, j, enc_map)
            chunk.update(probe_vals)
            msk = chunk["_mask"].astype(jnp.float32)
            d_s, d_q, d_m = _chunk_contrib(fs, meta_row, chunk, msk, L,
                                           False)
            a_s[...] = a_s[...] + d_s
            a_q[...] = a_q[...] + d_q
            a_m[...] = a_m[...] + d_m
            p_s[pl.ds(j, 1), :] = a_s[...]
            p_q[pl.ds(j, 1), :] = a_q[...]
            p_m[pl.ds(j, 1), :] = a_m[...]

        _for_chunks(chunk_step, i, B, C)

    _DISPATCHES[0] += 1
    outs = pl.pallas_call(
        body, grid=(pl.cdiv(C, B),),
        in_specs=[*col_specs, *probe_specs],
        out_specs=[*acc_specs, *row_specs],
        out_shape=[*acc_shapes, *row_shapes], interpret=interpret,
    )(*col_args, *probe_args)
    a_s, a_q, a_m, p_s, p_q, p_m = outs

    # scanned prefixes: integer-valued live counts — cumsum is exact, so
    # it matches the scan fold bit-for-bit (DESIGN.md §12)
    m32 = mask.astype(jnp.float32)
    scanned_chunks = jnp.sum(m32, axis=tuple(range(1, m32.ndim)))     # [C]
    zero = jnp.zeros((1,), jnp.float32)
    scanned_pref = jnp.concatenate([zero, jnp.cumsum(scanned_chunks)])

    init = gla.init()
    final = init._replace(
        sum=a_s[0, :A], sumsq=a_q[0, :A], matched=a_m[0, 0],
        scanned=init.scanned + scanned_pref[-1])
    pad_row = jnp.zeros((1, A_pad), jnp.float32)
    prefixes = init._replace(
        sum=jnp.concatenate([pad_row, p_s])[:, :A],
        sumsq=jnp.concatenate([pad_row, p_q])[:, :A],
        matched=jnp.concatenate([jnp.zeros((1, 1), jnp.float32), p_m])[:, 0],
        scanned=scanned_pref)
    return final, prefixes
