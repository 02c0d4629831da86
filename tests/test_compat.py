"""Pins repro.compat's one spelling of the jax 0.9 mesh APIs (DESIGN.md §6).

The last test enforces the layer's policy mechanically: no mesh/sharding
API spelled outside src/repro/compat.py — it is a thin wrapper over the
``compat-boundary`` lint rule (DESIGN.md §11), which owns the symbol
list and the exemptions.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat


def test_axis_type_dispatch():
    assert compat.AxisType is jax.sharding.AxisType


def test_make_mesh_defaults_to_auto_axes():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert isinstance(mesh, jax.sharding.Mesh)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert all(t == compat.AxisType.Auto for t in mesh.axis_types)


def test_ambient_mesh_roundtrip():
    """set_mesh scopes the mesh get_abstract_mesh sees."""
    assert compat.get_abstract_mesh().empty
    mesh = compat.make_mesh((1,), ("data",))
    with compat.set_mesh(mesh):
        ambient = compat.get_abstract_mesh()
        assert not ambient.empty
        assert dict(ambient.shape) == {"data": 1}
    assert compat.get_abstract_mesh().empty


def test_ambient_mesh_drives_constrain():
    """distributed.constraints is a no-op outside a mesh, active inside."""
    from repro.distributed.constraints import current_rules

    assert current_rules() is None
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.set_mesh(mesh):
        assert current_rules() is not None


def test_shard_map_unified_signature():
    """``check`` is the one spelling of jax.shard_map's check_vma."""
    mesh = compat.make_mesh((1,), ("data",))
    f = compat.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=(P("data"),), out_specs=P())
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_no_skew_symbol_outside_compat():
    """Thin wrapper over the compat-boundary lint rule (DESIGN.md §11):
    the rule owns the symbol list and the compat.py exemption."""
    from repro.analysis import lint_repo

    report = lint_repo(rules=["compat-boundary"])
    assert not report.findings, (
        "mesh/sharding jax APIs must go through repro/compat.py:\n"
        + "\n".join(f.render() for f in report.findings))
