"""Multi-process multihost validation (r3 VERDICT missing #2 / next #5).

jax.distributed bring-up with TWO real OS processes on CPU: a coordinator
and a peer form one PjRt cluster (gloo CPU collectives), build a global
mesh spanning both processes' devices, run a cross-process sharded
reduction, a sharded training step, and one served inference through the
full TpuEngine path on every process. This exercises the code path a TPU
pod uses over DCN — same initialize(), same global mesh, same
make_array_from_process_local_data — with gRPC+gloo standing in for the
pod's ICI/DCN transports.

Each subprocess gets the CPU platform from ``JAX_PLATFORMS`` in its
environment and sets its device count and collectives through jax.config
before first device use.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import sys
import jax

jax.config.update("jax_num_cpu_devices", 4)
jax.config.update("jax_cpu_collectives_implementation", "gloo")

pid = int(sys.argv[1])
port = sys.argv[2]

from client_tpu.parallel import multihost

got = multihost.initialize(f"127.0.0.1:{port}", 2, pid)
assert got == pid, (got, pid)
assert multihost.process_count() == 2
assert jax.process_count() == 2
assert len(jax.devices()) == 8, len(jax.devices())       # global
assert len(jax.local_devices()) == 4                     # per process

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# dp spans the two processes (slowest-varying axis -> cross-host traffic
# is dp-only, the multi-slice convention multihost.py documents).
mesh = multihost.global_mesh(axes=("dp", "tp"), shape={"dp": 2})
assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4

# -- cross-process sharded reduction ------------------------------------
sharding = NamedSharding(mesh, P("dp", None))
local = np.full((8, 4), pid + 1, np.float32)  # each host its own rows
arr = multihost.host_local_array((16, 4), sharding, local)
total = jax.jit(lambda x: jnp.sum(x),
                out_shardings=NamedSharding(mesh, P()))(arr)
assert float(total) == 8 * 4 * 1 + 8 * 4 * 2, float(total)
print(f"proc {pid}: reduction OK", flush=True)

# -- sharded training step over the global mesh -------------------------
# The train step's shardings use the dp x sp x tp convention; dp still
# spans the two processes.
from client_tpu.parallel.training import dryrun_training_step

train_mesh = multihost.global_mesh(axes=("dp", "sp", "tp"),
                                   shape={"dp": 2, "sp": 2})
dryrun_training_step(8, mesh=train_mesh)
print(f"proc {pid}: train step OK", flush=True)

# -- pipeline stages split ACROSS the processes (ppermute over DCN) -----
from client_tpu.parallel.pipeline import make_pipeline_train_step

pp_mesh = multihost.global_mesh(axes=("pp", "dp"), shape={"pp": 2})
assert pp_mesh.shape == {"pp": 2, "dp": 4}
pparams, popt, pstep, pshard = make_pipeline_train_step(pp_mesh, n_layers=2)
ptokens = pshard(np.random.default_rng(0).integers(0, 256, size=(3, 4, 17)))
pparams, popt, ploss = pstep(pparams, popt, ptokens)
assert np.isfinite(float(ploss))
print(f"proc {pid}: cross-host pipeline step OK", flush=True)

# -- experts split ACROSS the processes (dispatch all-to-all over DCN) --
from client_tpu.parallel.moe import make_moe_train_step

ep_mesh = multihost.global_mesh(axes=("ep", "dp", "tp"),
                                shape={"ep": 2, "dp": 2})
assert ep_mesh.shape["ep"] == 2
mparams, mopt, mstep, msharding = make_moe_train_step(
    ep_mesh, batch=8, seq=16)
mtokens = jax.device_put(
    jnp.asarray(np.random.default_rng(1).integers(0, 256, size=(8, 16)),
                jnp.int32), msharding)
mparams, mopt, mloss = mstep(mparams, mopt, mtokens)
assert np.isfinite(float(mloss))
print(f"proc {pid}: cross-host MoE step OK", flush=True)

# -- served inference through the engine on the global mesh -------------
from client_tpu.engine import InferRequest, TpuEngine
from client_tpu.engine.repository import ModelRepository
from client_tpu.parallel.serving import ShardedBertBackend

backend = ShardedBertBackend(
    mesh, name="bert_mh", seq_len=16, hidden=64, n_layers=2,
    n_heads=4, ffn=128, vocab=512, max_batch_size=8)
repo = ModelRepository()
repo.register_backend(backend)
engine = TpuEngine(repo)
try:
    ids = np.ones((2, 16), dtype=np.int32) * (3 + pid * 0)  # same on hosts
    mask = np.ones((2, 16), dtype=np.int32)
    resp = engine.infer(InferRequest(
        model_name="bert_mh",
        inputs={"input_ids": ids, "attention_mask": mask}), timeout_s=300)
    logits = np.asarray(resp.outputs["logits"])
    assert logits.shape[0] == 2 and np.isfinite(logits).all()
finally:
    engine.shutdown()
print(f"proc {pid}: served inference OK", flush=True)

# -- expert-parallel generative decode ACROSS the processes -------------
# Experts split over the two hosts: every decode wave's dispatch/combine
# all-to-all crosses DCN. One stream, fixed budget, no sampling: the
# dispatch sequence (1 prefill + N waves, bucket 1) is deterministic, so
# both processes issue identical jit executions in lockstep — the SPMD
# requirement — while each engine's scheduler runs on its own host.
import threading

from client_tpu.parallel.serving import MoeGptBackend

gen_mesh = multihost.global_mesh(axes=("ep", "tp"), shape={"ep": 2})
assert gen_mesh.shape["ep"] == 2
gbackend = MoeGptBackend(gen_mesh, name="moe_gpt_mh", n_layers=2,
                         d_model=64, n_heads=4, d_ff=128, vocab=256,
                         max_seq_len=32, max_streams=1)
grepo = ModelRepository()
grepo.register_backend(gbackend)
gengine = TpuEngine(grepo)
try:
    tokens, done = [], threading.Event()

    def gcb(resp):
        if resp.error is not None or resp.final:
            done.set()
        else:
            tokens.append(int(resp.outputs["TOKEN"][0]))

    gengine.async_infer(InferRequest(
        model_name="moe_gpt_mh",
        inputs={"INPUT_IDS": np.asarray([1, 2, 3], np.int32)},
        parameters={"max_tokens": 4}), gcb)
    assert done.wait(300), "cross-host generation stalled"
    assert len(tokens) == 4, tokens
finally:
    gengine.shutdown()
print(f"proc {pid}: cross-host expert decode OK tokens={tokens}",
      flush=True)
print(f"proc {pid}: ALL OK", flush=True)
"""


def _free_port() -> str:
    import socket

    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        return str(sk.getsockname()[1])


def test_two_process_cluster_mesh_train_and_serve(tmp_path):
    port = _free_port()
    env = dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu")
    env.pop("JAX_NUM_CPU_DEVICES", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, str(pid), port],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid}: ALL OK" in out, out
        assert f"proc {pid}: reduction OK" in out
        assert f"proc {pid}: train step OK" in out
        assert f"proc {pid}: cross-host pipeline step OK" in out
        assert f"proc {pid}: cross-host MoE step OK" in out
        assert f"proc {pid}: served inference OK" in out
        assert f"proc {pid}: cross-host expert decode OK" in out
    # both hosts decoded the same token stream (SPMD lockstep)
    tok_lines = [next(ln for ln in out.splitlines()
                      if "cross-host expert decode OK" in ln)
                 for out in outs]
    assert tok_lines[0].split("tokens=")[1] == \
        tok_lines[1].split("tokens=")[1]
