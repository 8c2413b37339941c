"""Multi-host mesh test: 2 real processes, loopback coordinator, CPU devices.

SURVEY §2.10 / §4 ("multi-host collectives tested on single host"): every
process calls jax.distributed.initialize (via parallel.init_multihost), the
global device list is the union of both processes' virtual-CPU devices, and
a pjit-sharded reduction over the global chain mesh sees every process's
shard. This is the same wiring a TPU pod slice uses; only the transport
(loopback gRPC vs ICI) differs.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")

from fleetflow_tpu import parallel

assert parallel.init_multihost(), "init_multihost returned single-process"
info = parallel.mesh_info()
assert info["process_count"] == 2, info
assert info["global_devices"] == 4, info

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = parallel.chain_mesh()
assert mesh.size == 4

# one row per global device, value = global position + 1 (device ids are
# NOT contiguous across processes; derive position from process index); the
# global sum is only correct if the reduction crossed both processes
sharding = NamedSharding(mesh, P("chains", None))
base = jax.process_index() * jax.local_device_count()
rows = [jax.device_put(jnp.full((1, 8), base + i + 1.0), d)
        for i, d in enumerate(jax.local_devices())]
arr = jax.make_array_from_single_device_arrays(
    (4, 8), sharding, rows)

total = jax.jit(lambda x: x.sum(), out_shardings=None)(arr)
expect = sum(range(1, 5)) * 8.0
assert float(total) == expect, (float(total), expect)

# the real solver across processes: a tiny service-axis sharded anneal
# whose pmin/psum collectives now ride the inter-process transport
from fleetflow_tpu.lower import synthetic_problem
from fleetflow_tpu.solver import prepare_problem
from fleetflow_tpu.solver.repair import verify
from fleetflow_tpu.solver.sharded import SVC_AXIS, anneal_sharded
from jax.sharding import Mesh
import numpy as np

pt = synthetic_problem(32, 8, seed=5)
prob = prepare_problem(pt)
svc_mesh = Mesh(np.array(jax.devices()), (SVC_AXIS,))
refined = anneal_sharded(prob, jnp.zeros((pt.S,), jnp.int32),
                         jax.random.PRNGKey(0), steps=200, mesh=svc_mesh,
                         block=200)
# gather the sharded result to every host for the exact check
from jax.experimental import multihost_utils
host_assign = np.asarray(
    multihost_utils.process_allgather(refined, tiled=True)).reshape(-1)[:pt.S]
stats_total = int(verify(pt, host_assign)["total"])

if jax.process_index() == 0:
    print("MULTIHOST_OK " + json.dumps({
        "total": float(total),
        "processes": info["process_count"],
        "global_devices": info["global_devices"],
        "sharded_anneal_violations": stats_total,
    }), flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_chain_mesh(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            FLEET_COORD=f"127.0.0.1:{port}",
            FLEET_NUM_PROCS="2",
            FLEET_PROC_ID=str(pid),
            PYTHONPATH=REPO,
        )
        env.pop("FLEET_FORCE_CPU", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env, cwd=str(tmp_path),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    for rc, out, err in outs:
        if rc != 0 and ("UNIMPLEMENTED" in err or "not supported" in err):
            pytest.skip(f"multi-process CPU collectives unsupported: "
                        f"{err.splitlines()[-1] if err else rc}")
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"

    marker = [l for rc, out, _ in outs for l in out.splitlines()
              if l.startswith("MULTIHOST_OK ")]
    assert marker, f"no result marker in {outs}"
    res = json.loads(marker[0][len("MULTIHOST_OK "):])
    assert res["processes"] == 2
    assert res["global_devices"] == 4
    assert res["sharded_anneal_violations"] == 0, res
