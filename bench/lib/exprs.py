"""Arithmetic expressions written as strings in the benchmark's data files.

A configuration names its aggregate expressions over columns
(``"l_extendedprice * (1 - l_discount)"``) and a traffic mix names its
predicate bounds over drawn parameters (``"DATE + 365"``).  Both are parsed
here into a tree of numbers, names, subscripts and ``+ - * /``, and nothing
else, so a data file can never run code.
"""
from __future__ import annotations

import ast
import math
import operator
from typing import Callable, Mapping

_BINOPS = {ast.Add: operator.add, ast.Sub: operator.sub,
           ast.Mult: operator.mul, ast.Div: operator.truediv}
_CONSTS = {"inf": math.inf}


def _check(node: ast.AST, text: str) -> None:
    for n in ast.walk(node):
        ok = isinstance(n, (ast.Expression, ast.BinOp, ast.UnaryOp,
                            ast.Constant, ast.Name, ast.Load, ast.Subscript,
                            ast.USub, ast.UAdd, *_BINOPS))
        if not ok:
            raise ValueError(f"{text!r}: {type(n).__name__} is not allowed")
        if isinstance(n, ast.Constant) and not isinstance(n.value, (int,
                                                                     float)):
            raise ValueError(f"{text!r}: constant {n.value!r} is not a number")
        if isinstance(n, ast.Subscript) and not (
                isinstance(n.slice, ast.Constant)
                and isinstance(n.slice.value, int)):
            raise ValueError(f"{text!r}: only integer subscripts are allowed")


def parse(text: str) -> Callable[[Mapping], object]:
    """Compile ``text`` into ``env -> value``; ``env`` maps names to numbers,
    lists or arrays.  The result is evaluated with the operands' own
    arithmetic, so NumPy float64 columns give a float64 value and JAX
    float32 columns a float32 one."""
    tree = ast.parse(text, mode="eval")
    _check(tree, text)

    def ev(n, env):
        if isinstance(n, ast.Expression):
            return ev(n.body, env)
        if isinstance(n, ast.Constant):
            return n.value
        if isinstance(n, ast.Name):
            if n.id in env:
                return env[n.id]
            if n.id in _CONSTS:
                return _CONSTS[n.id]
            raise KeyError(f"{text!r}: unknown name {n.id!r}")
        if isinstance(n, ast.Subscript):
            return ev(n.value, env)[n.slice.value]
        if isinstance(n, ast.UnaryOp):
            v = ev(n.operand, env)
            return -v if isinstance(n.op, ast.USub) else v
        return _BINOPS[type(n.op)](ev(n.left, env), ev(n.right, env))

    return lambda env: ev(tree, env)


def names(text: str) -> set:
    """The column or parameter names ``text`` reads."""
    return {n.id for n in ast.walk(ast.parse(text, mode="eval"))
            if isinstance(n, ast.Name) and n.id not in _CONSTS}
