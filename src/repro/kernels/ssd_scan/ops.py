"""jit'd public wrapper: dispatch Pallas kernel (TPU path) vs jnp ref."""
from functools import partial

import jax

from repro.kernels.ssd_scan.kernel import ssd_scan_pallas
from repro.kernels.ssd_scan.ref import ssd_chunked_ref


@partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk=128, use_pallas=False,
             interpret=False):
    if use_pallas:
        return ssd_scan_pallas(x, dt, A, Bm, Cm, D, chunk=chunk,
                               interpret=interpret)
    return ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
