"""Distributed-mode equivalence: the ring / packed shard_map gossip executors
and the shard-local compressor must agree with the dense single-device math.

These run in a subprocess with --xla_force_host_platform_device_count=8 so the
main pytest process keeps its single CPU device (see launch/dryrun.py notes).
"""

import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.core import make_topology, make_compressor
    from repro.core.gossip import (make_dense_mixer, make_ring_mixer,
                                   make_packed_mixer)

    mesh = make_mesh((4, 2), ("data", "model"))
    top = make_topology("ring", 4, weights="metropolis")
    key = jax.random.PRNGKey(0)
    # agent-stacked tree, second leaf model-sharded on its last dim
    tree = {"a": jax.random.normal(key, (4, 6, 8)),
            "b": jax.random.normal(key, (4, 10))}
    specs = {"a": P("data", None, "model"), "b": P("data", None)}
    sh = {k: NamedSharding(mesh, specs[k]) for k in specs}
    tree_sharded = {k: jax.device_put(tree[k], sh[k]) for k in tree}

    dense = make_dense_mixer(top.w)(tree)

    ring = make_ring_mixer(top.w, mesh, ("data",), leaf_specs=specs)
    out_ring = jax.jit(ring)(tree_sharded)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out_ring[k]),
                                   np.asarray(dense[k]), rtol=1e-5,
                                   atol=1e-6)
    print("ring-ok")

    # packed gossip is exact when the input is already block-sparse:
    # compress per (agent row x model shard) = per shard-local block
    comp = make_compressor("block_top_k", frac=0.25, block=4)
    def shard_local(t):
        f = jax.shard_map(lambda tt: jax.tree_util.tree_map(
            lambda l: comp(None, l), tt), mesh=mesh, in_specs=(specs,),
            out_specs=specs, check_vma=False)
        return f(t)
    sparse = jax.jit(shard_local)(tree_sharded)
    dense_on_sparse = make_dense_mixer(top.w)(
        jax.tree_util.tree_map(np.asarray, sparse))
    packed = make_packed_mixer(top.w, mesh, frac=0.25, agent_axes=("data",),
                               leaf_specs=specs)
    out_packed = jax.jit(packed)(sparse)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out_packed[k]),
                                   np.asarray(dense_on_sparse[k]), rtol=1e-4,
                                   atol=1e-5)
    print("packed-ok")

    # n=2 ring: both ppermute shifts deliver the same agent; the executor
    # must apply the neighbor once (regression: w_self*x + 2*w01*neighbor)
    mesh2 = make_mesh((2,), ("data",))
    top2 = make_topology("ring", 2, weights="metropolis")
    tree2 = {"a": jax.random.normal(key, (2, 5, 3)),
             "b": jax.random.normal(key, (2, 7))}
    specs2 = {"a": P("data", None, None), "b": P("data", None)}
    sh2 = {k: NamedSharding(mesh2, specs2[k]) for k in specs2}
    tree2_sharded = {k: jax.device_put(tree2[k], sh2[k]) for k in tree2}
    dense2 = make_dense_mixer(top2.w)(tree2)
    ring2 = make_ring_mixer(top2.w, mesh2, ("data",), leaf_specs=specs2)
    out2 = jax.jit(ring2)(tree2_sharded)
    for k in tree2:
        np.testing.assert_allclose(np.asarray(out2[k]),
                                   np.asarray(dense2[k]), rtol=1e-6,
                                   atol=1e-7)
    print("ring2-ok")

    # multi-pod ring seam: agent grid ('pod','data') on a (2,2,2) mesh
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    top4 = make_topology("ring", 4, weights="metropolis")
    specs3 = {"a": P(("pod", "data"), None, "model"),
              "b": P(("pod", "data"), None)}
    sh3 = {k: NamedSharding(mesh3, specs3[k]) for k in specs3}
    tree3 = {k: jax.device_put(tree[k], sh3[k]) for k in tree}
    ring3 = make_ring_mixer(top4.w, mesh3, ("pod", "data"),
                            leaf_specs=specs3)
    out3 = jax.jit(ring3)(tree3)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out3[k]),
                                   np.asarray(dense[k]), rtol=1e-5,
                                   atol=1e-6)
    print("multipod-ring-ok")

    # time-varying ring: a weight-rotating banded schedule keeps the
    # two-ppermute structure and only traces the band weights -- each
    # round must match the dense product with that round's W_t
    from repro.core.mixing import rotating_schedule
    sched = rotating_schedule(["ring/metropolis", "ring/lazy"], 4)
    ring_t = make_ring_mixer(sched.ws, mesh, ("data",), leaf_specs=specs)
    assert ring_t.time_varying
    jit_ring_t = jax.jit(ring_t)
    for t in range(3):
        want = make_dense_mixer(sched.ws[t % 2])(tree)
        got = jit_ring_t(tree_sharded, jnp.asarray(t, jnp.int32))
        for k in tree:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-5,
                                       atol=1e-6)
    print("ring-schedule-ok")

    # time-varying packed: the round's W enters the shard_map through the
    # same replicated slot; payload stays (values, indices) only
    packed_t = make_packed_mixer(sched.ws, mesh, frac=0.25,
                                 agent_axes=("data",), leaf_specs=specs)
    jit_packed_t = jax.jit(packed_t)
    for t in range(3):
        want = make_dense_mixer(sched.ws[t % 2])(
            jax.tree_util.tree_map(np.asarray, sparse))
        got = jit_packed_t(sparse, jnp.asarray(t, jnp.int32))
        for k in tree:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-4,
                                       atol=1e-5)
    print("packed-schedule-ok")
""")


def test_distributed_gossip_equivalence():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("ring-ok", "packed-ok", "ring2-ok", "multipod-ring-ok",
                   "ring-schedule-ok", "packed-schedule-ok"):
        assert marker in res.stdout, (marker, res.stdout, res.stderr[-2000:])
