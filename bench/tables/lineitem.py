"""TPC-H ``lineitem`` generated on the device from a seed, by dbgen's rules.

TPC-H v3 specification §4.2.3 defines the columns this benchmark keeps:

- ``O_ORDERDATE`` uniform on [STARTDATE, ENDDATE − 151 days];
- ``L_SHIPDATE`` = orderdate + U[1, 121]; ``L_RECEIPTDATE`` = shipdate +
  U[1, 30] (used for the flags only, not stored);
- ``L_RETURNFLAG`` R or A (even odds) if receiptdate ≤ CURRENTDATE, else N;
  ``L_LINESTATUS`` O if shipdate > CURRENTDATE, else F;
- ``L_QUANTITY`` U[1, 50]; ``L_DISCOUNT`` U{0.00 .. 0.10};
  ``L_TAX`` U{0.00 .. 0.08};
- ``L_EXTENDEDPRICE`` = quantity × ``P_RETAILPRICE`` of a uniform partkey,
  with retailprice = (90000 + ((partkey / 10) mod 20001) + 100 ·
  (partkey mod 1000)) / 100.

Dates are day numbers from STARTDATE.  returnflag × linestatus is stored as
one group id ``rfls`` in the order Q1 reports them: A|F, N|F, N|O, R|F (R|O
and A|O cannot occur, since a line is received after it ships).

Rows are independent and identically distributed, so the stored order is
already a uniformly random order: the property the estimators need.  Each
column is made by its own jitted call, so the peak stays near the resident
size; a column that depends on another reads the stored one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

FLAG_IDS = {("A", "F"): 0, ("N", "F"): 1, ("N", "O"): 2, ("R", "F"): 3}


def seed_key(seed: int):
    """A PRNG key from a seed of any size: the low 32 bits seed it and the
    bits above are folded in, so seeds past 2**32 stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _uniform_int(key, shape, lo, hi):
    """Uniform integers on the closed range [lo, hi]."""
    return jax.random.randint(key, shape, lo, hi + 1, dtype=jnp.int32)


def _ship(k, shape, d):
    order = _uniform_int(jax.random.fold_in(k, 0), shape, d["startdate"],
                         d["enddate"] - d["order_window_end_offset"])
    lo, hi = d["ship_offset"]
    return order + _uniform_int(jax.random.fold_in(k, 1), shape, lo, hi)


def _rfls(k, ship, d):
    lo, hi = d["receipt_offset"]
    receipt = ship + _uniform_int(jax.random.fold_in(k, 2), ship.shape, lo, hi)
    returned = receipt <= d["currentdate"]
    r_or_a = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.5, ship.shape)
    shipped = ship <= d["currentdate"]           # linestatus F
    af, nf, no, rf = (FLAG_IDS[f] for f in
                      (("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")))
    return jnp.where(returned, jnp.where(r_or_a, rf, af),
                     jnp.where(shipped, nf, no)).astype(jnp.int32)


def _quantity(k, shape, d):
    lo, hi = d["quantity"]
    return _uniform_int(jax.random.fold_in(k, 4), shape, lo,
                        hi).astype(jnp.float32)


def _extendedprice(k, qty, d, parts):
    partkey = _uniform_int(jax.random.fold_in(k, 5), qty.shape, 1, parts)
    cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    # quantity × cents <= 50 × 209900 < 2**24: exact in float32, so the
    # one rounding is the division to dollars
    return (qty * cents.astype(jnp.float32)) / jnp.float32(100)


def _hundredths(k, shape, lo_hi, salt):
    lo, hi = lo_hi
    n = _uniform_int(jax.random.fold_in(k, salt), shape, lo, hi)
    return n.astype(jnp.float32) / jnp.float32(100)


def generate(cfg: dict, seed: int, sharding) -> dict:
    """The configuration's lineitem as a ``[P, C, L]`` columns dict on the
    device(s) of ``sharding``, made from ``seed``."""
    P, L = cfg["partitions"], cfg["chunk_rows"]
    C = cfg["rows"] // (P * L)
    if P * C * L != cfg["rows"]:
        raise ValueError("rows must be a multiple of partitions × chunk_rows")
    shape = (P, C, L)
    d = cfg["dbgen"]
    parts = round(d["parts_per_sf"] * cfg["rows"] / d["rows_per_sf"])
    key = seed_key(seed)
    jit = functools.partial(jax.jit, out_shardings=sharding)
    out = {}
    out["l_shipdate"] = jit(lambda k: _ship(k, shape, d))(key)
    out["rfls"] = jit(lambda k, s: _rfls(k, s, d))(key, out["l_shipdate"])
    out["l_quantity"] = jit(lambda k: _quantity(k, shape, d))(key)
    out["l_extendedprice"] = jit(
        lambda k, q: _extendedprice(k, q, d, parts))(key, out["l_quantity"])
    out["l_discount"] = jit(
        lambda k: _hundredths(k, shape, d["discount_hundredths"], 6))(key)
    out["l_tax"] = jit(
        lambda k: _hundredths(k, shape, d["tax_hundredths"], 7))(key)
    out["_mask"] = jit(lambda: jnp.ones(shape, jnp.float32))()
    want = set(cfg["columns"])
    if set(out) != want:
        raise ValueError(f"lineitem makes {sorted(out)}, the configuration "
                         f"lists {sorted(want)}")
    return out
