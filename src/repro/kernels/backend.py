"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """`interpret` as given, or — when None — from the default backend:
    Mosaic-compiled on a TPU, the Pallas interpreter everywhere else.

    Every kernel entry and every caller that picks a mode defaults to
    None and resolves here, so a TPU never runs a kernel in interpret
    mode unless a caller asks for it by name.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
