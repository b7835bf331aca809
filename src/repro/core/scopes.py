"""The program's layers, as ``jax.named_scope`` writes them into the op
names of the compiled program, and so into a profiler trace of it.

Each layer's work runs inside the scope of its name:

* ``build``: node scaling, Morton sort and window geometry, kernel
  coefficients and spectral multiplier, the degree clamp and ``D^{-1/2}``;
* ``spread``: permutation into Morton order (where the caller works in its
  own node order), the window spread on either backend, fold of the pad,
  roll into FFT order;
* ``fft_mid``: rfftn -> multiply -> irfftn (or the hook that replaces it);
* ``gather``: roll, wrap pad, the window gather on either backend, inverse
  permutation (likewise);
* ``krylov``: the Krylov recurrence: Lanczos (start vector, recurrence,
  Ritz extraction) or CG (recurrence and the exit true-residual pass);
* ``krylov_orth``: the reorthogonalisation passes and the block QR.

One more scope marks a mechanism, not a layer: ``node_order`` holds a
Krylov solve's permutation of its inputs into the operator's own node order
and of its results back (:mod:`repro.core.node_order`), twice per solve and
never inside the Krylov loop.  It sits inside ``krylov``, and
:func:`innermost` passes over it, so its operations count to ``krylov``.

Scopes nest: the degree pass's spread, FFT and gather run inside the build,
and every operator application inside the Krylov recurrence.  An operation
belongs to the innermost scope around it (:func:`innermost`).  A scope adds
op-name metadata and nothing else: with the metadata stripped, the compiled
program is the same as without the scopes.
"""

from __future__ import annotations

import re

BUILD = "build"
SPREAD = "spread"
FFT_MID = "fft_mid"
GATHER = "gather"
KRYLOV = "krylov"
KRYLOV_ORTH = "krylov_orth"
SCOPES = (BUILD, SPREAD, FFT_MID, GATHER, KRYLOV, KRYLOV_ORTH)
NODE_ORDER = "node_order"

# a transformation wraps the names it applies to: transpose(jvp(fft_mid))
_TRANSFORMED = re.compile(r"^(\w+)\((.*)\)$")


def innermost(op_name: str) -> str | None:
    """The innermost scope in an op name (``jit(program)/krylov/while/body/
    spread/scatter-add``), or ``None`` outside every scope.

    The last part of an op name is the primitive that made the operation
    (``gather`` is one too), so it is never read as a scope; a jitted
    function (``jit(name)``) is not a scope either, nor is the mechanism's
    ``node_order``.
    """
    for part in reversed(op_name.split("/")[:-1]):
        m = _TRANSFORMED.match(part)
        while m and m.group(1) != "jit":
            part = m.group(2)
            m = _TRANSFORMED.match(part)
        if part in SCOPES:
            return part
    return None
