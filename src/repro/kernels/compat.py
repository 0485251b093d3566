"""Backend dispatch for the Pallas TPU kernels.

Whether a Pallas kernel runs compiled or through the interpreter is a
property of the *backend*, not of the call site.  Every kernel wrapper in
``repro.kernels.*.ops`` takes ``interpret=None`` and resolves it here:
compiled on TPU (the lowering these kernels are written against),
interpret/reference mode on every other backend.  Tests can still force
either mode with an explicit ``interpret=True/False`` argument; nothing
else can, so a run on the chip always executes the compiled kernels.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams


def default_interpret() -> bool:
    """True when Pallas kernels should run in interpreter/reference mode:
    every backend except TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> backend default; an explicit bool wins (test override)."""
    return default_interpret() if interpret is None else bool(interpret)
