"""The system under test's query family, built from a configuration file.

The configuration names its aggregate expressions, its predicate columns
and its group keys; this turns them into the program's ``SlotFamily`` and
a traffic slot into the program's ``SlotQuery``.
"""
from __future__ import annotations

from bench.lib import exprs


def _column_fn(fn):
    import jax.numpy as jnp

    def f(chunk):
        v = jnp.asarray(fn(chunk), jnp.float32)
        return jnp.broadcast_to(v, chunk["_mask"].shape)

    return f


def _group_fn(column):
    return lambda chunk: chunk[column]


def build_family(repro, cfg: dict):
    """``repro.SlotFamily`` over the configuration's expressions."""
    fns = {name: _column_fn(exprs.parse(text))
           for name, text in cfg["exprs"].items()}
    groups = {name: (_group_fn(g["column"]), int(g["num_groups"]))
              for name, g in cfg.get("groups", {}).items()}
    return repro.SlotFamily(exprs=fns, pred_cols=tuple(cfg["predicates"]),
                            groups=groups)


def slot_query(repro, slot: dict):
    """The program's ``SlotQuery`` for one traffic slot."""
    return repro.SlotQuery(expr=slot["expr"],
                           ranges={c: tuple(b)
                                   for c, b in slot["ranges"].items()},
                           group=slot.get("group"))
