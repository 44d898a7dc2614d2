"""Traced-jaxpr analysis: the static half of the invariant rules.

A jaxpr is what `jax.jit` will compile — walking it catches regressions
BEFORE any (slow) XLA compile: a scatter primitive sneaking onto the tiled
hot path, a narrowing `convert_element_type` appearing on an fp32-default
path. The walker recurses into every sub-jaxpr (cond/scan/jit/custom_vjp
bodies, `pallas_call` kernels), generalising the ad-hoc helper the
acceptance tests in `tests/test_aggregate.py` used to carry inline.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

__all__ = [
    "convert_ops",
    "count_primitives",
    "iter_eqns",
    "narrowing_converts",
    "primitive_names",
]


def _subjaxprs(value) -> Iterator:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)
    elif isinstance(value, dict):
        for v in value.values():
            yield from _subjaxprs(v)


def iter_eqns(jaxpr) -> Iterator:
    """Every equation in a (Closed)Jaxpr, recursing into sub-jaxprs
    (cond/scan/jit/custom_vjp/pallas_call bodies)."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in j.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def primitive_names(jaxpr) -> set:
    """All primitive names reachable from a (Closed)Jaxpr."""
    return {eqn.primitive.name for eqn in iter_eqns(jaxpr)}


def count_primitives(jaxpr) -> dict:
    """{primitive name: occurrence count} over the whole jaxpr tree."""
    counts: dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
    return counts


def convert_ops(jaxpr) -> dict:
    """{(src_dtype_name, dst_dtype_name): count} of every
    `convert_element_type` in the jaxpr tree."""
    out: dict[tuple, int] = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = np.dtype(eqn.invars[0].aval.dtype).name
        dst = np.dtype(eqn.params["new_dtype"]).name
        out[(src, dst)] = out.get((src, dst), 0) + 1
    return out


def narrowing_converts(jaxpr) -> dict:
    """Converts that SHRINK a floating payload: {(src, dst): count} where
    src is a float dtype of >= 4 bytes and dst is strictly smaller (bf16,
    f16, int8, fp8, ...). Integer index-width churn (i64 -> i32) and
    widenings (bool -> f32) are not wire compression and are ignored.
    """
    out: dict[tuple, int] = {}
    for (src, dst), n in convert_ops(jaxpr).items():
        sdt, ddt = np.dtype(src), np.dtype(dst)
        if (np.issubdtype(sdt, np.floating) and sdt.itemsize >= 4
                and ddt.itemsize < sdt.itemsize):
            out[(src, dst)] = out.get((src, dst), 0) + n
    return out
