"""Negative coverage for the dryrun remat gate (__graft_entry__).

The dryrun gate exists to fail configs whose shardings force XLA's
involuntary-full-rematerialization fallback. The positive path (a good
config passes) is covered by test_model_stack's dryrun tests; this file
proves the gate actually FIRES: a known-bad resharding compiles with the
"Involuntary full rematerialization" warning, and
``check_partitioner_output`` turns that captured output into an error.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest


def _graft():
    spec = importlib.util.spec_from_file_location(
        "graft_entry_remat", "__graft_entry__.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCheckPartitionerOutput:
    def test_clean_output_passes(self):
        mod = _graft()
        mod.check_partitioner_output("compiled ok\nno warnings here\n")

    def test_remat_warning_raises(self):
        mod = _graft()
        with pytest.raises(RuntimeError, match="rematerialization"):
            mod.check_partitioner_output(
                f"blah\n{mod.REMAT_WARNING} for op %dot.1\nblah\n"
            )

    def test_gspmd_deprecation_raises(self):
        mod = _graft()
        out = "W0000 GSPMD sharding propagation is going to be deprecated\n"
        with pytest.raises(RuntimeError, match="GSPMD"):
            mod.check_partitioner_output(out)


# A resharding the partitioner can only honor by replicating the whole
# tensor: dim 0 is laid out on mesh axis "a", then immediately demanded
# on ("a","b") over dim 1 — verified to print the involuntary-full-remat
# warning on jax's CPU backend with 8 forced devices.
_BAD_RESHARD = textwrap.dedent(
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.shard_map import shard_map  # noqa: F401  (forces SPMD init)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("a", "b", "c"))

    def f(x):
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P("a", None, None)))
        x = x * 2
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, ("a", "b"), None))
        )
        return x

    x = jnp.ones((8, 8, 4), jnp.float32)
    print(jax.jit(f)(x).sum())
    """
)


@pytest.mark.integ
class TestRematGateFires:
    def test_known_bad_sharding_trips_the_gate(self):
        mod = _graft()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        ).strip()
        proc = subprocess.run(
            [sys.executable, "-c", _BAD_RESHARD],
            env=env,
            capture_output=True,
            text=True,
            timeout=600,
        )
        output = proc.stdout + proc.stderr
        assert proc.returncode == 0, output  # it compiles — the gate is the catch
        assert mod.REMAT_WARNING in output, output
        with pytest.raises(RuntimeError, match="involuntary full remat"):
            mod.check_partitioner_output(output)
