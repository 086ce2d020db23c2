"""Multi-device collective tests.

The main pytest process must keep a single CPU device (smoke tests and the
benches depend on it), so these tests spawn subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"        # emulated devices, never a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.slow
def test_bbs_broadcast_all_candidates_all_port_ring():
    run_multidevice("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import topology as T
        from repro.core.bbs import build_plan
        from repro.core.intersection import ALL_PORT
        from repro.collectives import bbs_broadcast, make_device_schedule
        mesh = Mesh(np.array(jax.devices()), ('x',))
        plan = build_plan(T.ring(8), root=0, mode=ALL_PORT)
        x = jnp.arange(777, dtype=jnp.float32) - 3.5
        for cand in plan.candidates:
            sched = make_device_schedule(cand.pipeline, 8)
            out = bbs_broadcast(x, mesh, 'x', sched, num_groups=3)
            for i in range(8):
                np.testing.assert_allclose(out[i], x)
    """)


@pytest.mark.slow
def test_bbs_broadcast_nonzero_root_and_dtype():
    run_multidevice("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import topology as T
        from repro.core.bbs import build_plan
        from repro.core.intersection import FULL_DUPLEX
        from repro.collectives import bbs_broadcast, make_device_schedule
        mesh = Mesh(np.array(jax.devices()), ('x',))
        for root in (0, 3, 7):
            plan = build_plan(T.hypercube(3), root=root, mode=FULL_DUPLEX)
            for dtype in (jnp.float32, jnp.int32, jnp.bfloat16):
                x = jnp.arange(321).astype(dtype)
                sched = make_device_schedule(plan.candidates[0].pipeline, 8)
                out = bbs_broadcast(x, mesh, 'x', sched, num_groups=2)
                for i in range(8):
                    np.testing.assert_allclose(
                        np.asarray(out[i], np.float32),
                        np.asarray(x, np.float32))
    """)


@pytest.mark.slow
def test_baseline_collectives():
    run_multidevice("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.collectives import binomial_broadcast, chain_broadcast
        mesh = Mesh(np.array(jax.devices()), ('x',))
        x = jnp.linspace(-1, 1, 513, dtype=jnp.float32)
        for root in range(8):
            out = binomial_broadcast(x, mesh, 'x', root=root)
            for i in range(8):
                np.testing.assert_allclose(out[i], x)
        out = chain_broadcast(x, mesh, 'x', root=5, num_packets=7)
        for i in range(8):
            np.testing.assert_allclose(out[i], x)
    """)


@pytest.mark.slow
def test_bbs_broadcast_is_jittable_and_single_permute_per_round():
    """The lowered HLO must contain collective-permutes (not all-gathers) and
    compile cleanly under jit."""
    run_multidevice("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core import topology as T
        from repro.core.bbs import build_plan
        from repro.core.intersection import ALL_PORT
        from repro.collectives import bbs_broadcast, make_device_schedule
        mesh = Mesh(np.array(jax.devices()), ('x',))
        plan = build_plan(T.ring(8), root=0, mode=ALL_PORT)
        sched = make_device_schedule(plan.candidates[0].pipeline, 8)
        x = jnp.ones((4096,), jnp.float32)
        f = jax.jit(lambda v: bbs_broadcast(v, mesh, 'x', sched, num_groups=4))
        txt = f.lower(x).compile().as_text()
        assert 'collective-permute' in txt, 'expected ppermute lowering'
        out = f(x)
        for i in range(8):
            np.testing.assert_allclose(out[i], x)
    """)
