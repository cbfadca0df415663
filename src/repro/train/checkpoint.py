"""Sharded checkpointing: pytree -> directory of .npy leaves + manifest.

Layout:
    <dir>/manifest.json     {"leaves": {key: {"file", "shape", "dtype"}},
                             "step": int, "meta": {...}}
    <dir>/<key>.npy         one file per leaf (host-gathered)

Restore can re-shard onto any mesh via ``shardings`` (a matching pytree of
NamedSharding / PartitionSpec), so a checkpoint taken on one mesh restores
onto another — the paper's "naive global checkpointing" (§7) done properly.
"""
from __future__ import annotations

import json
import pathlib
import re
from typing import Any, Dict, Optional

import jax
import numpy as np


def _key_str(path) -> str:
    parts = []
    for p in path:
        name = getattr(p, "key", None)
        if name is None:
            name = getattr(p, "idx", None)
        if name is None:
            name = getattr(p, "name", str(p))
        parts.append(str(name))
    key = ".".join(parts)
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", key)


def save_checkpoint(ckpt_dir: str, tree: Any, step: int = 0,
                    meta: Optional[Dict] = None) -> None:
    d = pathlib.Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    leaves = {}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        key = _key_str(path)
        arr = np.asarray(jax.device_get(leaf))
        fn = f"{key}.npy"
        np.save(d / fn, arr)
        leaves[key] = {"file": fn, "shape": list(arr.shape),
                       "dtype": str(arr.dtype)}
    (d / "manifest.json").write_text(json.dumps(
        {"leaves": leaves, "step": step, "meta": meta or {}}, indent=2))


def load_leaf(path, dtype: str) -> np.ndarray:
    """Load one leaf file with the dtype its manifest recorded. ``np.save``
    writes an ml_dtypes array (``bfloat16``) as raw void records, which
    ``np.load`` returns as ``|V2``; viewing them back is bitwise."""
    arr = np.load(path)
    if arr.dtype.kind == "V":
        arr = arr.view(np.dtype(dtype))
    return arr


def load_checkpoint(ckpt_dir: str, like: Any, shardings: Any = None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs). Returns (tree, step)."""
    d = pathlib.Path(ckpt_dir)
    manifest = json.loads((d / "manifest.json").read_text())
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    shard_flat = (jax.tree_util.tree_flatten(shardings)[0]
                  if shardings is not None else [None] * len(flat))
    out = []
    for (path, leaf), sh in zip(flat, shard_flat):
        key = _key_str(path)
        if key not in manifest["leaves"]:
            raise KeyError(f"checkpoint missing leaf {key}")
        entry = manifest["leaves"][key]
        arr = load_leaf(d / entry["file"], entry["dtype"])
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"{key}: shape {arr.shape} != {leaf.shape}")
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), manifest["step"]
