"""jit'd public wrapper: dispatch Pallas kernel (TPU path) vs jnp ref."""
from functools import partial

import jax

from repro.kernels.flash_decode.kernel import flash_decode_pallas
from repro.kernels.flash_decode.ref import flash_decode_partial_ref


@partial(jax.jit, static_argnames=("k_offset", "sliding_window",
                                   "use_pallas", "interpret"))
def flash_decode_partial(q, k, v, *, cur_pos, k_offset=0, sliding_window=0,
                         use_pallas=False, interpret=False):
    if use_pallas:
        return flash_decode_pallas(q, k, v, cur_pos=cur_pos,
                                   k_offset=k_offset,
                                   sliding_window=sliding_window,
                                   interpret=interpret)
    return flash_decode_partial_ref(q, k, v, cur_pos=cur_pos,
                                    k_offset=k_offset,
                                    sliding_window=sliding_window)
