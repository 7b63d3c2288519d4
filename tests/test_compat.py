"""The sharding entry points (repro.utils.compat) on the installed jax.

Partial-auto shard_map: a strict subset of the mesh axes goes Manual and
the rest stays Auto/GSPMD, which keeps the ``model`` axis tensor-parallel
inside the FL round.  The ring test needs two devices and so runs in a
subprocess (XLA_FLAGS must be set before jax initialises); everything else
runs on ONE device (a 1x1 mesh).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import compat

P = jax.sharding.PartitionSpec
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_partial_auto_pipelined_ring_matches_full_manual():
    """The double-buffered ring collective (``pipeline_hops=True``, the
    default) lowered with the `model` axis left Auto (partial-auto
    shard_map) must aggregate bit-identically to the fully-Manual
    lowering — the pipelined ppermute scan must survive the GSPMD
    partitioner handling the Auto axis around it."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.config.base import QuantConfig
    from repro.core import aggregation as agg
    from repro.utils import compat
    P = jax.sharding.PartitionSpec

    assert jax.device_count() == 2
    mesh = compat.make_mesh((2, 1), ("data", "model"))
    qcfg = QuantConfig(bits=8, use_pallas=True)  # pipeline_hops defaults on
    plan = agg.make_wire_plan("ring", qcfg, ("data",), (2,))
    assert plan.effective == "ring"
    d = 4096
    delta = jax.random.normal(jax.random.PRNGKey(0), (2, d), jnp.float32)
    lam = jnp.ones((2,), jnp.float32)
    key = jax.random.PRNGKey(3)

    def body(dl, l, k):
        out = agg.aggregate(plan, {"w": dl[0]}, jnp.float32(0.5), l[0], k)
        return out["w"]

    outs = {}
    with compat.set_mesh(mesh):
        for names in ({"data"}, {"data", "model"}):
            f = jax.jit(compat.shard_map(
                body, mesh=mesh, in_specs=(P("data"), P("data"), P()),
                out_specs=P(), check_vma=False, axis_names=names))
            outs[frozenset(names)] = np.asarray(f(delta, lam, key))
    np.testing.assert_array_equal(outs[frozenset({"data"})],
                                  outs[frozenset({"data", "model"})])
    print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout


def test_partial_auto_keeps_model_axis_auto():
    """With axis_names={'data'} the `model` axis must stay Auto inside the
    body (its Manual axes are {'data'}) — the tensor-parallel FL-round
    regime."""
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    seen = {}

    def body(x):
        seen["manual"] = frozenset(
            jax.sharding.get_abstract_mesh().manual_axes)
        return jax.lax.psum(x, ("data",))

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                         out_specs=P(), check_vma=False,
                         axis_names={"data"})
    with compat.set_mesh(mesh):
        x = jnp.arange(4.0)
        np.testing.assert_allclose(np.asarray(jax.jit(f)(x)), np.asarray(x))
    assert seen["manual"] == frozenset({"data"})
