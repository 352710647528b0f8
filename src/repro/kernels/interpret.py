"""Whether the Pallas kernels run in the interpreter.

The kernels compile with Mosaic unless the process asks for the
interpreter: ``REPRO_PALLAS_INTERPRET=1`` in the environment (the CPU test
suite sets it in ``tests/conftest.py``; CPU tools such as the auditor set
it for themselves), or ``interpret=True`` at a kernel call.  Nothing
depends on the backend, so a run that lands on the CPU by accident fails
at its first kernel instead of interpreting in silence, and a TPU run
never interprets: asking for the interpreter there is an error.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

ENV = "REPRO_PALLAS_INTERPRET"


def resolve(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else the process setting (``ENV``).  Raises
    when the interpreter is asked for on a TPU backend."""
    if interpret is None:
        interpret = os.environ.get(ENV, "") not in ("", "0")
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"Pallas interpret mode requested on a TPU backend (unset {ENV} "
            "and pass no interpret=True): on the chip every kernel compiles "
            "with Mosaic")
    return bool(interpret)
