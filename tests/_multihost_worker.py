"""Subprocess body for the REAL two-process ``jax.distributed`` test.

Each worker pins itself to a 4-device virtual CPU backend, joins the
coordination service, builds the hybrid ICI x DCN mesh through
``MultihostRuntime`` (the exact production entry point), and executes
cross-process collectives whose results it asserts locally.  The parent
test only checks exit codes + the OK marker — all numeric assertions
happen inside the distributed processes themselves, like the
reference's CT peer-node suites (SURVEY.md §4: multi-node on one host).

Usage: python _multihost_worker.py <rank> <num_processes> <port>
"""

import os
import re
import sys


def main() -> None:
    rank, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", "")).strip()
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

    import jax  # noqa: E402
    import numpy as np  # noqa: E402

    # pin the config too, as tests/conftest.py does: the workers run
    # on the CPU whatever the machine holds
    jax.config.update("jax_platforms", "cpu")

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from emqx_tpu.parallel.multihost import MultihostRuntime

    rt = MultihostRuntime.from_env(
        coordinator=f"127.0.0.1:{port}",
        num_processes=nproc, process_id=rank)
    assert rt.initialized, "two-process bootstrap fell back to passthrough"
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.process_index() == rank
    assert len(jax.local_devices()) == 4
    assert len(jax.devices()) == 4 * nproc, "global device view incomplete"
    assert rt.is_coordinator() == (rank == 0)

    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    # hybrid mesh: dp (outer, crosses processes = DCN), tp (inner = ICI)
    mesh = rt.hybrid_mesh({"tp": 4}, dcn_axis="dp")
    assert dict(mesh.shape) == {"dp": nproc, "tp": 4}, dict(mesh.shape)
    # outer-axis rows must each live on ONE process (DCN only between rows)
    for row in mesh.devices:
        assert len({d.process_index for d in row}) == 1, mesh.devices

    base = np.arange(nproc * 4, dtype=np.float32).reshape(nproc, 4)
    arr = jax.make_array_from_callback(
        base.shape, NamedSharding(mesh, P("dp", "tp")),
        lambda idx: base[idx])

    # collective 1: jitted global sum (all-reduce across both fabrics);
    # the scalar result is fully replicated, so every process can read
    # its own addressable copy
    total = jax.jit(lambda x: x.sum())(arr)
    got = float(np.asarray(total.addressable_shards[0].data))
    assert got == float(base.sum()), (got, base.sum())

    # collective 2: explicit psum over the DCN axis via shard_map
    g = shard_map(lambda b: jax.lax.psum(b, "dp"), mesh=mesh,
                  in_specs=P("dp", "tp"), out_specs=P(None, "tp"))
    out = g(arr)
    col_sums = base.sum(axis=0)
    for shard in out.addressable_shards:
        local = np.asarray(shard.data).ravel()
        tp_col = shard.index[1].start or 0
        assert np.allclose(local, col_sums[tp_col:tp_col + local.size]), (
            rank, local, col_sums)

    # collective 3: ppermute ring over the cross-process axis — the
    # ring_fanout tile-rotation schedule's fabric, proven on real DCN
    ring = shard_map(
        lambda b: jax.lax.ppermute(
            b, "dp", [(i, (i + 1) % nproc) for i in range(nproc)]),
        mesh=mesh, in_specs=P("dp", "tp"), out_specs=P("dp", "tp"))
    rolled = ring(arr)
    want_rolled = np.roll(base, 1, axis=0)
    for shard in rolled.addressable_shards:
        assert np.allclose(np.asarray(shard.data),
                           want_rolled[shard.index]), (
            rank, shard.index, np.asarray(shard.data))

    jax.distributed.shutdown()
    print(f"MULTIHOST_OK rank={rank}", flush=True)


if __name__ == "__main__":
    main()
