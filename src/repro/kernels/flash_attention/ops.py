"""jit'd public wrapper: dispatch Pallas kernel (TPU path) vs jnp ref."""
from functools import partial

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "sliding_window", "q_offset",
                                   "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal=True, sliding_window=0, q_offset=0,
                    use_pallas=False, interpret=False):
    if use_pallas:
        return flash_attention_pallas(q, k, v, causal=causal,
                                      sliding_window=sliding_window,
                                      q_offset=q_offset, interpret=interpret)
    return flash_attention_ref(q, k, v, causal=causal,
                               sliding_window=sliding_window,
                               q_offset=q_offset)
