"""Shared Pallas backend detection for every kernel in this package.

One policy, one place: a Pallas kernel compiles natively where a Mosaic
backend exists (TPU).  Interpret mode (``interpret=True``, the *same*
traced kernel body executed through XLA without the hardware lowering)
is for tests on a CPU: bit-for-bit the same program, minus the speed.

Every kernel entry point takes ``interpret: Optional[bool] = None`` and
resolves it through :func:`resolve_interpret`, so

* library code simply omits the argument and gets the right mode for the
  host (``flash_attention_op`` on TPU compiles, on CPU interprets);
* tests/benchmarks can force either mode explicitly;
* the decision logic is not re-sniffed per module (it used to live as
  ``ops.py::_interpret()`` and would have been copy-pasted into each new
  kernel).
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["has_compiled_backend", "use_interpret", "resolve_interpret"]

# backends with a native Pallas (Mosaic) lowering for these kernels
_COMPILED_BACKENDS = ("tpu",)


def has_compiled_backend() -> bool:
    """True when the default JAX backend can compile Pallas kernels
    natively (rather than executing them under the interpreter)."""
    return jax.default_backend() in _COMPILED_BACKENDS


def use_interpret() -> bool:
    """The default ``interpret=`` value for a Pallas call on this host."""
    return not has_compiled_backend()


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an explicit/omitted ``interpret`` argument: ``None`` means
    "whatever this host needs" (:func:`use_interpret`); a bool is taken
    at face value."""
    return use_interpret() if interpret is None else bool(interpret)
