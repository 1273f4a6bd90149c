"""Where the Pallas kernels run: compiled by Mosaic on a TPU, in the Pallas
interpreter everywhere else.

:func:`interpret_mode` is the single source of every ``pallas_call``'s
``interpret=`` flag (the ``interpret-pinned`` lint rule in
``repro.analysis.lint`` enforces that). It is derived from the platform and
has no override, so a TPU process can never run the interpreter and a CPU
process (the test suite) always does.
"""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """True unless JAX's default backend is a TPU. Read at trace time, so a
    process sees one value for its whole life."""
    return jax.default_backend() != "tpu"
