"""jit'd public wrapper: dispatch Pallas kernel (TPU path) vs jnp ref."""
from functools import partial

import jax

from repro.kernels.softmax_xent.kernel import xent_local_stats_pallas
from repro.kernels.softmax_xent.ref import local_stats_ref


@partial(jax.jit, static_argnames=("vocab_offset", "use_pallas", "interpret"))
def xent_local_stats(logits, labels, vocab_offset=0, *, use_pallas=False,
                     interpret=False):
    if use_pallas:
        return xent_local_stats_pallas(logits, labels, vocab_offset,
                                       interpret=interpret)
    return local_stats_ref(logits, labels, vocab_offset)
