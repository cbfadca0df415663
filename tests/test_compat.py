"""The jax API wrappers (repro/compat.py) and the vma helpers built on the
installed jax: ``jax.lax.pcast(..., to="varying")`` replaced the deprecated
``jax.lax.pvary``, and ``jax.typeof`` replaced ``jax.core.get_aval``.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import repro.compat


class TestPvaryShim:
    def test_pvary_resolves_on_current_jax(self):
        """``pcast`` to varying widens the vma inside shard_map — the
        primitive ``force_vary`` and the train-step metrics rely on."""
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1,), ("data",))

        def f(x):
            y = jax.lax.pcast(x, ("data",), to="varying")
            assert "data" in jax.typeof(y).vma
            return y

        out = repro.compat.shard_map(f, mesh, P(), P("data"),
                                     check=True)(jnp.ones(3))
        np.testing.assert_array_equal(np.asarray(out), np.ones(3))

    def test_force_vary_routes_through_compat(self):
        """models/common.py uses the installed jax's pcast, not the
        deprecated pvary — outside shard_map force_vary is a no-op."""
        import repro.models.common as common

        src = open(common.__file__).read()
        assert "jax.lax.pcast" in src
        assert "pvary" not in src
        x = jnp.ones((2, 2))
        assert common.force_vary(x, ("data",)) is x  # no live axes -> no-op

    def test_train_steps_route_through_compat(self):
        import repro.train.steps as steps

        src = open(steps.__file__).read()
        assert "jax.lax.pvary" not in src
