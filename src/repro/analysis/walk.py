"""Recursive jaxpr traversal shared by the repro.analysis checkers.

Jaxprs nest: ``pjit``/``closed_call`` carry a ClosedJaxpr, ``scan`` /
``while`` / ``cond`` carry body/branch jaxprs, ``custom_jvp_call`` /
``custom_vjp_call`` carry a primal ``call_jaxpr``, ``shard_map`` a plain
``jaxpr``. Every checker needs the same walk with a human-readable path
(for Finding.where), so it lives here once.

``iter_eqns`` yields every equation in the whole tree (depth-first) with
its path; ``sub_jaxprs`` enumerates the direct children of one equation —
the unit the key-discipline checker recurses on (it analyzes each scope's
internal use pattern separately, because a scan body's carry key is a
FRESH key every iteration and must not be conflated with the outer init
key's uses).
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
from jax.extend import core as jcore


def _as_jaxpr(obj):
    """ClosedJaxpr | Jaxpr -> Jaxpr (None for anything else)."""
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, jcore.Jaxpr):
        return obj
    return None


def sub_jaxprs(eqn) -> List[Tuple[str, "jcore.Jaxpr"]]:
    """The (label, jaxpr) children of one equation, in params order.

    Labels disambiguate multi-jaxpr primitives ("cond:branch0",
    "while:body") and carry the pjit name when one exists
    ("pjit:_normal") so Finding paths read like call stacks.
    """
    out: List[Tuple[str, "jcore.Jaxpr"]] = []
    name = eqn.params.get("name")
    for pname, val in eqn.params.items():
        vals = list(val) if isinstance(val, (list, tuple)) else [val]
        for i, v in enumerate(vals):
            j = _as_jaxpr(v)
            if j is None:
                continue
            label = eqn.primitive.name
            if name and pname == "jaxpr":
                label = f"{label}:{name}"
            elif pname not in ("jaxpr", "call_jaxpr"):
                label = f"{label}:{pname}"
            if isinstance(val, (list, tuple)) and len(vals) > 1:
                label = f"{label}{i}"
            out.append((label, j))
    return out


def iter_eqns(jaxpr, path: str = "") -> Iterator[Tuple[str, "jcore.JaxprEqn"]]:
    """Depth-first (path, eqn) over ``jaxpr`` and every nested jaxpr."""
    j = _as_jaxpr(jaxpr)
    for eqn in j.eqns:
        yield path, eqn
        for label, sub in sub_jaxprs(eqn):
            sub_path = f"{path}/{label}" if path else label
            yield from iter_eqns(sub, sub_path)


def is_key_var(var) -> bool:
    """True for typed-PRNG-key avals (key<fry>[...]): the registry traces
    every driver program with typed keys precisely so key identity is
    visible in the jaxpr as a first-class dtype."""
    aval = getattr(var, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return False
    try:
        return jax.dtypes.issubdtype(aval.dtype, jax.dtypes.prng_key)
    except TypeError:  # pragma: no cover - exotic avals
        return False


def aval_str(var) -> str:
    aval = getattr(var, "aval", None)
    return str(aval) if aval is not None else "?"
