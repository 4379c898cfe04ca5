"""Multi-host (DCN) runtime: hybrid mesh construction + collective
routing on the virtual 8-device CPU mesh, with host count simulated —
the laptop-to-fleet passthrough contract of parallel/multihost.py."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from emqx_tpu.parallel import (
    MultihostRuntime, dcn_env, hybrid_mesh_from,
)
from jax.sharding import NamedSharding, PartitionSpec as P


def test_single_process_passthrough():
    rt = MultihostRuntime.from_env()
    assert rt.num_processes == 1 and not rt.initialized
    assert rt.is_coordinator()
    mesh = rt.hybrid_mesh({"tp": 2}, dcn_axis="dp")
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == len(jax.devices()) // 2


def test_env_contract():
    os.environ["EMQX_TPU_NUM_PROCESSES"] = "1"
    try:
        env = dcn_env()
        assert env["num_processes"] == "1"
        rt = MultihostRuntime.from_env()
        assert not rt.initialized      # 1 process -> passthrough
    finally:
        del os.environ["EMQX_TPU_NUM_PROCESSES"]


def test_hybrid_mesh_groups_hosts_on_outer_axis():
    """Simulate 2 hosts x 4 devices: inner axes must only span devices
    of one simulated host (ICI); the outer axis crosses hosts (DCN)."""
    devs = jax.devices()
    mesh = hybrid_mesh_from({"tp": 2}, dcn_axis="dp", devices=devs,
                            num_hosts=2)
    assert dict(mesh.shape) == {"dp": 4, "tp": 2}
    arr = mesh.devices
    # each dp row holds devices from ONE simulated host (ids 0-3 | 4-7)
    for row in range(4):
        host_ids = {d.id // 4 for d in arr[row]}
        assert len(host_ids) == 1, arr


def test_hybrid_mesh_collectives_route_correctly():
    """psum over the inner axis + all_gather over the outer axis give
    the same numbers as a flat computation."""
    from jax.experimental.shard_map import shard_map

    mesh = hybrid_mesh_from({"tp": 4}, dcn_axis="dp", num_hosts=2)
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

    def f(block):
        # block: (4, 1) per device — reduce over tp, keep dp shards
        return jax.lax.psum(block, "tp")

    g = shard_map(f, mesh=mesh, in_specs=P("dp", "tp"),
                  out_specs=P("dp", "tp"))
    got = np.asarray(g(x))
    # psum over tp sums the 4 column shards within each dp row group
    assert np.allclose(got, np.broadcast_to(
        np.asarray(x).sum(axis=1, keepdims=True), (8, 4)))


def test_hybrid_mesh_rejects_bad_factorizations():
    with pytest.raises(ValueError):
        hybrid_mesh_from({"tp": 3}, num_hosts=2)     # 4 % 3 != 0
    with pytest.raises(ValueError):
        hybrid_mesh_from({"dp": 2}, dcn_axis="dp", num_hosts=2)
    with pytest.raises(ValueError):
        hybrid_mesh_from({"tp": 2}, num_hosts=3)     # 8 % 3 != 0


def test_leftover_devices_fold_into_dcn_axis():
    # 2 hosts x 4 devices, ici uses only 2 -> outer = hosts x leftover
    mesh = hybrid_mesh_from({"tp": 2}, dcn_axis="dp", num_hosts=2)
    assert dict(mesh.shape) == {"dp": 4, "tp": 2}
    assert mesh.devices.size == 8


def test_two_process_jax_distributed_collectives():
    """REAL two-process ``jax.distributed`` — spawn 2
    OS processes, bootstrap the coordination service on localhost, build
    the hybrid ICI x DCN mesh, and run psum / global-sum / ppermute
    collectives ACROSS processes.  All numeric assertions run inside the
    workers (tests/_multihost_worker.py); this parent checks the
    bootstrap + both OK markers."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker = os.path.join(os.path.dirname(__file__), "_multihost_worker.py")
    env = dict(os.environ)
    # the worker pins its own JAX env; scrub the parent's 8-device flag
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(rank), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        for rank in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any("aren't implemented on the CPU backend" in o for o in outs):
        pytest.skip("jax CPU backend lacks multiprocess collectives")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"MULTIHOST_OK rank={rank}" in out, out
