"""Pallas kernels.  Every kernel entry point takes ``interpret=None``:
the compiled kernel on a TPU backend, the Pallas interpreter elsewhere."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> interpret exactly when the backend is not a TPU."""
    return jax.default_backend() != "tpu" if interpret is None else interpret
