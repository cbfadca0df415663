"""Picklable lowering recipes — how a worker process rebuilds its stages.

``runtime="processes"`` ships each executor's spec builder to one worker per
node id (:mod:`repro.runtime.process`). A lowered program cannot make that
trip: jitted callables, vjp closures and ``jax.sharding.Mesh`` objects are
process-local. What *can* travel is the recipe the driver lowered from — the
logical graph, the SBP plan, the stage partition and a device-id description
of the mesh — so each worker re-runs the same deterministic lowering against
its own XLA client and jit-compiles only the stages it actually fires.

:class:`MeshSpec` is the wire form of a mesh: axis names + shape + flat
device ids, rebuilt against the worker's device table (workers inherit the
driver's ``XLA_FLAGS`` via :mod:`repro.launch.xla_env`, so the tables match).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A ``jax.sharding.Mesh`` as data: rebuildable in any process that sees
    the same device table."""

    axis_names: Tuple[str, ...]
    shape: Tuple[int, ...]
    device_ids: Tuple[int, ...]

    @classmethod
    def capture(cls, mesh) -> Optional["MeshSpec"]:
        if mesh is None:
            return None
        import numpy as np

        devs = np.asarray(mesh.devices)
        return cls(tuple(mesh.axis_names), tuple(devs.shape),
                   tuple(int(d.id) for d in devs.ravel()))

    def to_mesh(self):
        import jax
        import numpy as np

        table = {d.id: d for d in jax.devices()}
        missing = [i for i in self.device_ids if i not in table]
        if missing:
            raise RuntimeError(
                f"mesh device id(s) {missing} absent in this process "
                f"({len(table)} devices visible); runtime='processes' "
                "workers must see the driver's device table — check "
                "XLA_FLAGS=--xla_force_host_platform_device_count")
        arr = np.array([table[i] for i in self.device_ids],
                       dtype=object).reshape(self.shape)
        return jax.sharding.Mesh(arr, self.axis_names)


def _resolve_meshes(graph, mesh: Optional[MeshSpec],
                    stage_meshes: Optional[Tuple[MeshSpec, ...]]):
    """Mirror ``repro.api.compile``'s mesh defaulting: an explicit mesh spec
    wins, else the graph placement's mesh — unless per-stage meshes are
    given, in which case the shared mesh stays None."""
    if mesh is not None:
        shared = mesh.to_mesh()
    elif stage_meshes is None:
        shared = graph.placement.to_mesh()
    else:
        shared = None
    per_stage = ([m.to_mesh() for m in stage_meshes]
                 if stage_meshes is not None else None)
    return shared, per_stage


@dataclasses.dataclass
class InferRecipe:
    """Everything :func:`repro.core.lowering.lower_stages` needs, as data."""

    graph: Any
    plan: Any
    partition: Any
    mesh: Optional[MeshSpec] = None
    stage_meshes: Optional[Tuple[MeshSpec, ...]] = None

    def lower(self):
        from repro.core.lowering import lower_stages

        shared, per_stage = _resolve_meshes(self.graph, self.mesh,
                                            self.stage_meshes)
        return lower_stages(self.graph, self.plan, self.partition,
                            mesh=shared, stage_meshes=per_stage)


@dataclasses.dataclass
class TrainRecipe:
    """Everything :func:`repro.core.lowering.lower_train_stages` needs, as
    data. ``loss`` is a tensor name (or LTensor); the optimizer's ``lr``
    must be a float or module-level callable to survive pickling."""

    graph: Any
    plan: Any
    partition: Any
    param_names: List[str]
    loss: Any = None
    mesh: Optional[MeshSpec] = None
    stage_meshes: Optional[Tuple[MeshSpec, ...]] = None
    optimizer: Any = None

    def lower(self):
        from repro.core.lowering import lower_train_stages

        shared, per_stage = _resolve_meshes(self.graph, self.mesh,
                                            self.stage_meshes)
        return lower_train_stages(self.graph, self.plan, self.partition,
                                  list(self.param_names), loss=self.loss,
                                  mesh=shared, stage_meshes=per_stage,
                                  optimizer=self.optimizer)


@dataclasses.dataclass
class ServeRecipe:
    """Everything :func:`repro.core.lowering.lower_serve_stages` needs, as
    data. ``params`` are host (numpy) copies of the model params."""

    cfg: Any
    params: Dict[str, Any]
    num_stages: int
    cache_len: int
    max_prompt_len: int
    group_size: int
    mesh: Optional[MeshSpec] = None

    def lower(self):
        from repro.core.lowering import lower_serve_stages
        from repro.launch.mesh import make_mesh

        mesh = (self.mesh.to_mesh() if self.mesh is not None
                else make_mesh((1, 1), ("data", "model")))
        return lower_serve_stages(self.cfg, mesh, self.params,
                                  num_stages=self.num_stages,
                                  cache_len=self.cache_len,
                                  max_prompt_len=self.max_prompt_len,
                                  group_size=self.group_size)
