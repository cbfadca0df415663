"""Thin wrappers over the jax APIs the repo uses in many places.

:func:`shard_map` keeps one spelling of ``jax.shard_map`` across the code
base: ``check`` is its ``check_vma`` flag (off by default — the vma
inference is advisory and rejects some valid psum-synced programs).
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
