"""jit'd wrapper for the SSD chunk-scan kernel (model layout adapter)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bh
from repro.kernels.ssd_scan.ref import ssd_scan_ref


@partial(jax.jit, static_argnames=("chunk", "use_kernel", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk=128, use_kernel=True, interpret=None):
    """Model layout: x (b, s, h, p); dt (b, s, h); A (h,); B/C (b, s, g, n)
    with g == 1 (groups broadcast outside).  Returns y (b, s, h, p)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(b * h, s)
    Af = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h)
    Bf = jnp.broadcast_to(B[:, :, 0:1, :], (b, s, h, n)) \
            .transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Cf = jnp.broadcast_to(C[:, :, 0:1, :], (b, s, h, n)) \
            .transpose(0, 2, 1, 3).reshape(b * h, s, n)
    if use_kernel:
        y = ssd_scan_bh(xf, dtf, Af, Bf, Cf, chunk=chunk, interpret=interpret)
    else:
        y = ssd_scan_ref(xf, dtf, Af, Bf, Cf)
    return y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
