"""Recursive jaxpr traversal shared by the jaxpr-level analyzers.

`walk_eqns` yields every equation in a closed jaxpr and all its
sub-jaxprs (pjit bodies, while cond/body, scan bodies, cond branches,
custom_* rules) with a structural path, so analyzers can tell whether
an op sits inside a loop body. `loops` yields each `while`/`scan`
equation together with its carried avals — for `while` the body
jaxpr's outputs *are* the carry; for `scan` the first ``num_carry``
outputs are, plus the enclosing loop's carries that it takes as
consts.
"""
from __future__ import annotations

from typing import Any, FrozenSet, Iterator, List, Tuple


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for vv in vs:
            if isinstance(vv, ClosedJaxpr):
                yield vv.jaxpr
            elif isinstance(vv, Jaxpr):
                yield vv


def walk_eqns(jaxpr, path: Tuple[str, ...] = ()) -> Iterator[
        Tuple[Tuple[str, ...], Any]]:
    """Yield ``(path, eqn)`` for every equation, depth-first. ``path``
    is the chain of enclosing primitive names (e.g. ``("pjit",
    "while", "scan")``)."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from walk_eqns(sub, path + (eqn.primitive.name,))


def in_loop(path: Tuple[str, ...]) -> bool:
    return "while" in path or "scan" in path


def loops(jaxpr, path: Tuple[str, ...] = (),
          carried: FrozenSet[int] = frozenset()
          ) -> Iterator[Tuple[Tuple[str, ...], Any, List[Any]]]:
    """Yield ``(path, eqn, carry_avals)`` for every while/scan.

    ``carried`` holds the ids of the variables that are loop state of
    an enclosing loop. A scan (what ``fori_loop`` lowers to) passes a
    carry that its body returns unchanged in as a const operand, so
    such a const is still state the inner loop holds: it is counted
    with the scan's carries."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        subs = list(_sub_jaxprs(eqn.params))
        if name == "while":
            body = eqn.params["body_jaxpr"].jaxpr
            yield path, eqn, [v.aval for v in body.outvars]
            state = body.invars[eqn.params["body_nconsts"]:]
            subs = [(eqn.params["cond_jaxpr"].jaxpr, frozenset()),
                    (body, frozenset(map(id, state)))]
        elif name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            nk, nc = eqn.params["num_consts"], eqn.params["num_carry"]
            fwd = [i for i, v in enumerate(eqn.invars[:nk])
                   if id(v) in carried]
            yield path, eqn, ([v.aval for v in body.outvars[:nc]]
                              + [eqn.invars[i].aval for i in fwd])
            state = ([body.invars[i] for i in fwd]
                     + body.invars[nk:nk + nc])
            subs = [(body, frozenset(map(id, state)))]
        else:
            # pjit / closed_call / cond branches take the trailing
            # operands positionally
            subs = [(sub, frozenset(
                id(iv) for iv, ov in zip(sub.invars[::-1],
                                         eqn.invars[::-1])
                if id(ov) in carried)) for sub in subs]
        for sub, state in subs:
            yield from loops(sub, path + (name,), state)
