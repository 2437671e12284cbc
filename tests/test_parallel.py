"""Multi-chip sharding tests on the 8-device virtual CPU mesh."""

import os

import numpy as np

from client_tpu.parallel.mesh import make_mesh, mesh_axes

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
from client_tpu.parallel.training import dryrun_training_step


class TestMesh:
    def test_axes_product(self):
        for n in (1, 2, 4, 6, 8):
            sizes = mesh_axes(n)
            assert np.prod(list(sizes.values())) == n

    def test_make_mesh_8(self):
        mesh = make_mesh(8)
        assert mesh.devices.size == 8
        assert set(mesh.axis_names) == {"dp", "sp", "tp"}
        assert all(s > 1 for s in mesh.shape.values())  # all axes real at 8

    def test_make_mesh_subset(self):
        mesh = make_mesh(4)
        assert mesh.devices.size == 4


class TestTraining:
    def test_dryrun_step_8dev(self):
        dryrun_training_step(8)

    def test_dryrun_step_2dev(self):
        dryrun_training_step(2)


class TestGraftEntry:
    def test_entry_compiles(self):
        import jax

        import __graft_entry__ as ge

        fn, args = ge.entry()
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)
        assert out["logits"].shape == (8, 2)
        assert out["pooled_output"].shape == (8, 768)

    def test_dryrun_multichip(self):
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)


class TestMultihost:
    def test_global_mesh_and_host_local_array(self):
        """Single-process instance of the multi-host pattern: global mesh
        over all devices, per-process batch assembly, pjit consumption."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from client_tpu.parallel import multihost

        assert multihost.process_count() == 1
        mesh = multihost.global_mesh(axes=("dp", "tp"))
        assert set(mesh.shape.keys()) == {"dp", "tp"}

        sharding = NamedSharding(mesh, P("dp", None))
        local = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        arr = multihost.host_local_array((16, 4), sharding, local)
        assert arr.shape == (16, 4)
        total = jax.jit(lambda x: jnp.sum(x))(arr)
        assert float(total) == float(local.sum())

    def test_global_mesh_pinned_shape(self):
        from client_tpu.parallel import multihost

        mesh = multihost.global_mesh(axes=("dp", "tp"),
                                     shape={"dp": 4, "tp": 2})
        assert mesh.shape["dp"] == 4 and mesh.shape["tp"] == 2
        # Partial pin: the free axis size is inferred from the device count.
        mesh = multihost.global_mesh(axes=("dp", "tp"), shape={"dp": 2})
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4

    def test_initialize_single_process(self):
        """jax.distributed single-process bring-up in a clean interpreter
        (initialize must precede backend init, so not in-process here)."""
        import subprocess
        import sys

        code = (
            "from client_tpu.parallel import multihost\n"
            "pid = multihost.initialize('127.0.0.1:19765', 1, 0)\n"
            "assert pid == 0, pid\n"
            "assert multihost.process_count() == 1\n"
            "pid2 = multihost.initialize('127.0.0.1:19765', 1, 0)\n"
            "assert pid2 == 0  # idempotent\n"
            "print('MULTIHOST-OK')\n"
        )
        env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120,
                              env=env)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "MULTIHOST-OK" in proc.stdout
