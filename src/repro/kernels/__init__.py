"""Pallas kernels for the signature fold (see `sig_fold`) and the
host-side layouts they consume (see `ops`)."""
from __future__ import annotations

from typing import Optional


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs interpreted: on the CPU backend yes,
    on any other backend it is compiled.  Every kernel of the package
    resolves its ``interpret=None`` default here, so a TPU run never
    falls back to the interpreter in silence; an explicit bool (a
    compile-only test targeting a described TPU) wins."""
    if interpret is not None:
        return bool(interpret)
    import jax
    return jax.default_backend() == "cpu"
