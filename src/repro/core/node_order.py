"""The node order a Krylov solve runs in.

A fastsum operator keeps its nodes' window geometry in Morton order
(:func:`repro.core.nfft.build_window_geometry`), and its ``matvec`` works in
the caller's node order: every application permutes the input into Morton
order and the output back.  The operator is ``P^T A_M P``, with ``A_M`` the
operator in Morton order and ``P`` the permutation, and Lanczos and CG do not
care which basis they run in: Lanczos on ``A`` from ``v0`` makes ``P^T``
times the iterates of Lanczos on ``A_M`` from ``P v0``, and CG likewise.  So
a solve can permute its inputs in once and its results out once, and run
every application in Morton order in between.

An operator that can apply itself in its own order says so with a
``node_order()`` method, which returns a :class:`NodeOrder` (or ``None``).
:func:`of` finds it on the callable a Krylov entry point is handed: the
bound ``matvec`` method of such an operator (``op.matvec`` of a
``NormalizedAdjacencyOperator`` or of a square ``FastsumOperator``).  A bare
callable, a rectangular operator or a bank has none, and its solve runs in
the caller's order as before.  :func:`rows` and :func:`nodes` permute in
and out inside the ``node_order`` scope (:mod:`repro.core.scopes`), so a
trace shows the two of them once per solve, outside the Krylov loop.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax

from repro.core import scopes

Array = jax.Array


class NodeOrder(NamedTuple):
    """An operator's own node order.

    perm: (n,) int32 — row ``r`` of the order holds node ``perm[r]``.
    inv: (n,) int32 — node ``i`` sits in row ``inv[i]``.
    matvec: the same operator applied to vectors whose rows are in this
      order (``x[perm] -> (A x)[perm]``), with no permutation of its own.
    """

    perm: Array
    inv: Array
    matvec: Callable[[Array], Array]


def of(matvec) -> NodeOrder | None:
    """The node order of the operator whose bound ``matvec`` this is, or
    ``None`` for any other callable."""
    owner = getattr(matvec, "__self__", None)
    node_order = getattr(owner, "node_order", None)
    if getattr(matvec, "__name__", None) != "matvec" or \
            not callable(node_order):
        return None
    return node_order()


def rows(order: NodeOrder, *xs):
    """Node vectors (n,) or (n, C) in the order's rows; ``None`` stays."""
    with jax.named_scope(scopes.NODE_ORDER):
        return tuple(None if x is None else x[order.perm] for x in xs)


def nodes(order: NodeOrder, x: Array) -> Array:
    """Rows back in node order: the inverse of :func:`rows`."""
    with jax.named_scope(scopes.NODE_ORDER):
        return x[order.inv]
