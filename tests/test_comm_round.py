"""Comm-round engine tests: the Pallas (interpret-mode) backend must match
the pure-jnp reference path bit-for-close for every algorithm that routes
through CommRound, across odd, non-tile-aligned pytree shapes (flat-plane
padding correctness), and the wire-byte metric must be uniform across
algorithms.

These tests run without hypothesis and are never skipped, so ef_track /
ef_step / ef_gossip are always exercised via interpret=True on CPU CI.

The model-sharded (per-shard planes) parity tests run in a subprocess with
--xla_force_host_platform_device_count=8 so this process keeps its single
CPU device (same pattern as tests/test_distributed_gossip.py).
"""

import functools
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CommRound, PorterConfig, make_compressor, make_mixer,
                        make_porter_step, make_topology, porter_init)
from repro.core import baselines as BL
from repro.core.comm_round import compress_stacked
from repro.core.porter_adam import make_porter_adam_step, porter_adam_init
from repro.kernels import flatten as FL
from repro.kernels import ops, ref

N = 5  # agents

# odd, non-tile-aligned shapes: scalar leaf, non-multiple-of-8 vector, 3-D
# leaf, and one leaf that crosses a tile boundary (8*1024 elements per tile)
ODD_PARAMS = {
    "b": jnp.zeros(()),
    "w": jnp.zeros((123,)),
    "k": jnp.zeros((7, 11, 3)),
    "big": jnp.zeros((9000,)),
}


def _loss_fn(params, batch):
    f, l = batch
    f = jnp.atleast_2d(f)
    l = jnp.atleast_1d(l)
    pred = (f @ params["w"] + params["b"] + jnp.sum(params["k"])
            + jnp.mean(params["big"]))
    return jnp.mean((pred - l) ** 2)


def _batch(key, n=N, b=4):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (n, b, 123)),
            jax.random.normal(k2, (n, b)))


def _top():
    return make_topology("erdos_renyi", N, weights="best_constant", p=0.9,
                         seed=2)


def _tree_allclose(a, b, atol=1e-5):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# per-leaf plane application: dtypes, odd shapes, structure checks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stacked", [True, False])
def test_plane_apply_roundtrip_odd_shapes(stacked):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, len(ODD_PARAMS))
    lead = (N,) if stacked else ()
    tree = {name: jax.random.normal(k, lead + p.shape).astype(
                jnp.float32 if i % 2 == 0 else jnp.bfloat16)
            for i, (k, (name, p)) in enumerate(zip(ks, ODD_PARAMS.items()))}
    seen = []

    def kernel(j, a, b):
        seen.append(j)
        return a.astype(jnp.float32) + b.astype(jnp.float32), a

    twice, same = FL.plane_apply(kernel, (tree, tree), 2)
    assert seen == list(range(len(tree)))
    for name in tree:
        assert twice[name].dtype == same[name].dtype == tree[name].dtype
        np.testing.assert_array_equal(np.asarray(same[name], np.float32),
                                      np.asarray(tree[name], np.float32))
        np.testing.assert_array_equal(
            np.asarray(twice[name], np.float32),
            2 * np.asarray(tree[name], np.float32))


def test_ef_kernels_reject_mismatched_operands():
    a, b = jnp.zeros((4, 8)), jnp.zeros((4, 9))
    with pytest.raises(ValueError, match="same-shape"):
        ops.ef_gossip(a, a, a, a, b, 0.3, interpret=True)


def test_plane_apply_rejects_mismatched_trees():
    with pytest.raises(ValueError, match="same-structure"):
        FL.plane_apply(lambda j, a, b: (a,),
                       ({"a": jnp.zeros((4, 3))},
                        {"a": jnp.zeros((4, 3)), "b": jnp.zeros((4, 3))}), 1)


# ---------------------------------------------------------------------------
# ef_gossip kernel vs oracle (ef_track/ef_step sweeps live in test_kernels)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [1, 123, 8192, 9000])
@pytest.mark.parametrize("scale", [1.0, 0.5])
def test_ef_gossip_matches_ref(d, scale):
    keys = jax.random.split(jax.random.PRNGKey(d), 5)
    q, m, y, c, wc = [jax.random.normal(k, (d,)) for k in keys]
    out_k = ops.ef_gossip(q, m, y, c, wc, 0.37, scale, interpret=True)
    out_r = ref.ef_gossip_ref(q, m, y, c, wc, 0.37, scale)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_ef_track_and_step_fused_semantics():
    """The engine's pallas path == running ef_track/ef_step on flat planes
    == the jnp reference, on a non-tile-aligned buffer."""
    d = 355
    keys = jax.random.split(jax.random.PRNGKey(1), 7)
    q, m, v, c, wc, g, gp = [jax.random.normal(k, (d,)) for k in keys]
    qo, mo, vo = ops.ef_track(q, m, v, c, wc, g, gp, 0.2, interpret=True)
    qr, mr, vr = ref.ef_track_ref(q, m, v, c, wc, g, gp, 0.2)
    for a, b in zip((qo, mo, vo), (qr, mr, vr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    xo = ops.ef_step(q, m, v, c, wc, g, 0.2, 0.05, interpret=True)
    xr = ref.ef_step_ref(q, m, v, c, wc, g, 0.2, 0.05)
    for a, b in zip(xo, xr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# engine parity: pallas(interpret) vs ref across algorithms and variants
# ---------------------------------------------------------------------------

def _porter_cfg(variant):
    top = _top()
    gamma = 0.5 * (1 - top.alpha) * 0.1
    sigma = 0.05 if variant == "dp" else 0.0
    return top, PorterConfig(eta=0.03, gamma=gamma, tau=1.0, variant=variant,
                             sigma_p=sigma)


@pytest.mark.parametrize("variant,comp_name",
                         [("gc", "top_k"), ("dp", "random_k"),
                          ("beer", "block_top_k")])
def test_porter_engine_parity(variant, comp_name):
    """PORTER-GC/DP/BEER: pallas interpret backend == jnp reference backend
    after several steps, odd shapes, atol 1e-5."""
    top, cfg = _porter_cfg(variant)
    comp = make_compressor(comp_name, frac=0.1)
    mixer = make_mixer(top, "dense")
    state_ref = state_pal = porter_init(ODD_PARAMS, N, w=top.w)
    step_ref = jax.jit(make_porter_step(cfg, _loss_fn, mixer, comp,
                                        backend="ref"))
    step_pal = jax.jit(make_porter_step(cfg, _loss_fn, mixer, comp,
                                        backend="pallas", interpret=True))
    key = jax.random.PRNGKey(7)
    for _ in range(3):
        key, kb, ks = jax.random.split(key, 3)
        batch = _batch(kb)
        state_ref, m_ref = step_ref(state_ref, batch, ks)
        state_pal, m_pal = step_pal(state_pal, batch, ks)
    for field in ("x", "v", "q_x", "q_v", "m_x", "m_v", "g_prev"):
        _tree_allclose(getattr(state_ref, field), getattr(state_pal, field))
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_pal["loss"]),
                               rtol=1e-5)
    assert float(m_ref["wire_bytes"]) == float(m_pal["wire_bytes"]) > 0


def test_porter_adam_engine_parity():
    top, cfg = _porter_cfg("gc")
    comp = make_compressor("top_k", frac=0.1)
    mixer = make_mixer(top, "dense")
    state_ref = state_pal = porter_adam_init(ODD_PARAMS, N, w=top.w)
    step_ref = jax.jit(make_porter_adam_step(cfg, _loss_fn, mixer, comp,
                                             backend="ref"))
    step_pal = jax.jit(make_porter_adam_step(cfg, _loss_fn, mixer, comp,
                                             backend="pallas",
                                             interpret=True))
    key = jax.random.PRNGKey(9)
    for _ in range(3):
        key, kb, ks = jax.random.split(key, 3)
        batch = _batch(kb)
        state_ref, _ = step_ref(state_ref, batch, ks)
        state_pal, _ = step_pal(state_pal, batch, ks)
    _tree_allclose(state_ref.base.x, state_pal.base.x)
    _tree_allclose(state_ref.m, state_pal.m)
    _tree_allclose(state_ref.s, state_pal.s)


def test_choco_engine_parity():
    top = _top()
    comp = make_compressor("top_k", frac=0.1)
    mixer = make_mixer(top, "dense")
    gamma = 0.3 * (1 - top.alpha) * 0.1
    eng_pal = CommRound(compressor=comp, mixer=mixer, backend="pallas",
                        interpret=True)
    state_ref = state_pal = BL.choco_init(ODD_PARAMS, N)
    step_ref = jax.jit(functools.partial(BL.choco_step, 0.03, gamma,
                                         _loss_fn, mixer, comp))
    step_pal = jax.jit(functools.partial(BL.choco_step, 0.03, gamma,
                                         _loss_fn, mixer, comp,
                                         engine=eng_pal))
    key = jax.random.PRNGKey(11)
    for _ in range(3):
        key, kb, ks = jax.random.split(key, 3)
        batch = _batch(kb)
        state_ref, m_ref = step_ref(state_ref, batch, ks)
        state_pal, m_pal = step_pal(state_pal, batch, ks)
    for field in ("x", "q", "m"):
        _tree_allclose(getattr(state_ref, field), getattr(state_pal, field))
    assert float(m_ref["wire_bytes"]) == float(m_pal["wire_bytes"]) > 0


# ---------------------------------------------------------------------------
# engine invariants and metrics schema
# ---------------------------------------------------------------------------

def test_engine_preserves_mirror_identity():
    """m == W q after every engine round (the wire-protocol identity),
    through the pallas path."""
    top, cfg = _porter_cfg("gc")
    comp = make_compressor("top_k", frac=0.2)
    mixer = make_mixer(top, "dense")
    state = porter_init(ODD_PARAMS, N, w=top.w)
    step = jax.jit(make_porter_step(cfg, _loss_fn, mixer, comp,
                                    backend="pallas", interpret=True))
    key = jax.random.PRNGKey(3)
    for _ in range(4):
        key, kb, ks = jax.random.split(key, 3)
        state, _ = step(state, _batch(kb), ks)
    w = jnp.asarray(top.w, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(state.m_x["w"]),
        np.asarray(jnp.einsum("ij,jd->id", w, state.q_x["w"])),
        rtol=1e-3, atol=1e-5)


def test_wire_bytes_uniform_schema():
    """Every algorithm reports wire_bytes; PORTER moves 2x CHOCO's stream
    and DSGD pays the dense price."""
    top = _top()
    comp = make_compressor("top_k", frac=0.05)
    mixer = make_mixer(top, "dense")
    eng = CommRound(compressor=comp, mixer=mixer)
    d = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(ODD_PARAMS))
    one_stream = eng.wire_bytes(d, n_agents=N)
    assert one_stream > 0
    # dense identity: full n*d*4 bytes
    ident = CommRound(compressor=make_compressor("identity"), mixer=mixer)
    assert ident.wire_bytes(d, n_agents=N) == pytest.approx(4.0 * N * d)
    # sparse stream strictly cheaper than dense
    assert one_stream < ident.wire_bytes(d, n_agents=N)

    key = jax.random.PRNGKey(5)
    batch = _batch(key)
    _, cfg = _porter_cfg("gc")
    pstate = porter_init(ODD_PARAMS, N, w=top.w)
    pstep = jax.jit(make_porter_step(cfg, _loss_fn, mixer, comp))
    _, pm = pstep(pstate, batch, key)
    cstate = BL.choco_init(ODD_PARAMS, N)
    cstep = jax.jit(functools.partial(BL.choco_step, 0.03, 0.01, _loss_fn,
                                      mixer, comp))
    _, cm = cstep(cstate, batch, key)
    dstate = BL.dsgd_init(ODD_PARAMS, N)
    dstep = jax.jit(functools.partial(BL.dsgd_step, 0.03, 1.0, _loss_fn,
                                      mixer))
    _, dm = dstep(dstate, batch, key)
    sstate = BL.soteria_init(ODD_PARAMS, N)
    sstep = jax.jit(functools.partial(BL.soteria_step, 0.03, 0.5, _loss_fn,
                                      comp, tau=1.0, sigma_p=0.01))
    _, sm = sstep(sstate, batch, key)
    for m in (pm, cm, dm, sm):
        assert "wire_bytes" in m and "loss" in m
    # PORTER gossips two compressed streams, CHOCO one
    assert float(pm["wire_bytes"]) == pytest.approx(2 * float(cm["wire_bytes"]))
    # consensus reported by all decentralized algorithms
    for m in (pm, cm, dm):
        assert "consensus_x" in m
    # DSGD uncompressed: strictly more bytes than CHOCO's sparse stream
    assert float(dm["wire_bytes"]) > float(cm["wire_bytes"])


def test_engine_rejects_unknown_backend():
    comp = make_compressor("top_k", frac=0.1)
    with pytest.raises(ValueError):
        CommRound(compressor=comp, mixer=None, backend="cuda")


# ---------------------------------------------------------------------------
# per-shard planes: model-sharded mesh parity + collective inspection
# ---------------------------------------------------------------------------

def test_engine_without_mesh_keeps_single_plane_path():
    comp = make_compressor("top_k", frac=0.1)
    eng = CommRound(compressor=comp, mixer=make_mixer(_top(), "dense"),
                    backend="pallas", interpret=True)
    assert eng._plane_mesh() is None


def test_engine_on_a_mesh_uses_per_shard_planes():
    # agent-only specs too: a Mosaic call outside shard_map would run on
    # gathered buffers
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    comp = make_compressor("top_k", frac=0.1)
    mesh = make_mesh((1,), ("data",))
    eng = CommRound(compressor=comp, mixer=make_mixer(_top(), "dense"),
                    backend="pallas", interpret=True, mesh=mesh,
                    leaf_specs={"w": P("data", None)})
    assert eng._plane_mesh() is not None


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.api import ExperimentSpec, build_engine, resolve_compressor
    from repro.launch.steps import make_shard_local_compress

    mesh = make_mesh((4, 2), ("data", "model"))
    n = 4
    key = jax.random.PRNGKey(0)

    # odd, non-tile-aligned leaves; 'a'/'c' model-sharded, 'b' replicated
    # over the model axis
    shapes = {"a": (n, 7, 6), "b": (n, 123), "c": (n, 10, 2)}
    specs = {"a": P("data", None, "model"), "b": P("data", None),
             "c": P("data", None, "model")}
    sh = {k: NamedSharding(mesh, specs[k]) for k in specs}

    def tree(k, dtype=jnp.float32):
        ks = jax.random.split(k, len(shapes))
        return {name: jax.device_put(
                    jax.random.normal(kk, shapes[name]).astype(dtype),
                    sh[name])
                for kk, name in zip(ks, shapes)}

    ks = jax.random.split(key, 6)
    y, q, m, g, gp = (tree(k) for k in ks[:5])
    kr = ks[5]

    base = ExperimentSpec(n_agents=n, topology="ring",
                          compressor="block_top_k", frac=0.25,
                          compressor_kwargs={"block": 4})
    comp = resolve_compressor(base)
    shard_local = make_shard_local_compress(comp, mesh, specs)

    def engines(gossip_mode):
        kw = dict(mesh=mesh, leaf_specs=specs, compress_fn=shard_local)
        ref = build_engine(base.replace(gossip_mode=gossip_mode,
                                        comm_backend="ref"), **kw)
        pal = build_engine(base.replace(gossip_mode=gossip_mode,
                                        comm_backend="pallas",
                                        interpret=True), **kw)
        assert pal._plane_mesh() is not None, "per-shard planes inactive"
        return ref, pal

    def check(tref, tpal, atol=1e-5, rtol=1e-5):
        for name in tref:
            np.testing.assert_allclose(
                np.asarray(tref[name], np.float32),
                np.asarray(tpal[name], np.float32), atol=atol, rtol=rtol)

    # --- parity: track / step / gossip_apply, ring + packed wire formats ---
    for mode in ("ring", "packed"):
        ref, pal = engines(mode)
        vr, qr, mr = jax.jit(lambda k: ref.track(k, y, q, m, g, gp, 0.2))(kr)
        vp, qp, mp = jax.jit(lambda k: pal.track(k, y, q, m, g, gp, 0.2))(kr)
        for a, b in ((vr, vp), (qr, qp), (mr, mp)):
            check(a, b)
        xr, _, _ = jax.jit(lambda k: ref.step(k, y, q, m, vr, 0.2, 0.05))(kr)
        xp, _, _ = jax.jit(lambda k: pal.step(k, y, q, m, vp, 0.2, 0.05))(kr)
        check(xr, xp)
        yr, _, _ = jax.jit(lambda k: ref.gossip_apply(k, y, q, m, 0.2, 0.5))(kr)
        yp, _, _ = jax.jit(lambda k: pal.gossip_apply(k, y, q, m, 0.2, 0.5))(kr)
        check(yr, yp)
        print(mode + "-parity-ok")

    # --- bf16 buffer dtype through the per-shard planes ---
    yb, qb, mb, gb, gpb = (tree(k, jnp.bfloat16) for k in ks[:5])
    ref, pal = engines("ring")
    vr, qr, mr = jax.jit(lambda k: ref.track(k, yb, qb, mb, gb, gpb, 0.2))(kr)
    vp, qp, mp = jax.jit(lambda k: pal.track(k, yb, qb, mb, gb, gpb, 0.2))(kr)
    for name in vr:
        assert vp[name].dtype == jnp.bfloat16, vp[name].dtype
    # ref accumulates in bf16, the kernel in f32 -- parity up to bf16 ulps
    for a, b in ((vr, vp), (qr, qp), (mr, mp)):
        check(a, b, atol=6e-2, rtol=6e-2)
    print("bf16-parity-ok")

    # --- collective inspection: pack/unpack must add no all-gather --------
    from repro.analysis.hlo import collective_counts

    def ag_count(eng):
        f = jax.jit(lambda k, y, q, m, g, gp: eng.track(k, y, q, m, g, gp,
                                                        0.2),
                    in_shardings=(NamedSharding(mesh, P()),) + (sh,) * 5)
        txt = f.lower(kr, y, q, m, g, gp).compile().as_text()
        return collective_counts(txt)["all-gather"]

    ref, pal = engines("ring")
    # ring gossip + shard-local compression + per-shard planes: the whole
    # round is ppermutes only -- zero all-gathers anywhere in the HLO
    assert ag_count(pal) == 0, "pallas ring track lowered an all-gather"
    print("ring-no-allgather-ok")

    ref, pal = engines("packed")
    # packed gossip all-gathers (value, index) pairs over the *agent* axis
    # in both backends; per-shard planes must not add model-axis gathers
    n_ref, n_pal = ag_count(ref), ag_count(pal)
    assert n_pal <= n_ref, (n_pal, n_ref)
    print("packed-no-extra-allgather-ok")
""")


def test_sharded_engine_parity_and_collectives():
    """Tentpole oracle: on a data x model host mesh, backend='pallas'
    (interpret, per-shard planes) matches backend='ref' to atol 1e-5 for
    track/step/gossip_apply on odd shapes (+ bf16 buffers), and the plane
    pack/unpack introduces no all-gather over the model axis."""
    res = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("ring-parity-ok", "packed-parity-ok", "bf16-parity-ok",
                   "ring-no-allgather-ok", "packed-no-extra-allgather-ok"):
        assert marker in res.stdout, (marker, res.stdout,
                                      res.stderr[-2000:])


def test_packed_wire_bytes_per_leaf_and_shard_windows():
    """Engine packed accounting matches the executor's padding: one window
    count per leaf and per model shard, not ceil(sum(d)/PACK_BLOCK)."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from repro.core.gossip import PACK_BLOCK

    comp = make_compressor("block_top_k", frac=0.05)

    def packed_mixer():
        mix = lambda t: t  # noqa: E731 -- wire-mode tag carrier only
        mix.wire_mode, mix.wire_frac = "packed", 0.05
        return mix

    k_b = max(round(0.05 * PACK_BLOCK), 1)
    tree = {"b": jnp.zeros((4, 123)), "w": jnp.zeros((4, 42))}
    eng = CommRound(compressor=comp, mixer=packed_mixer())
    # the executor pads each leaf separately: 2 windows, not ceil(165/2048)=1
    assert eng.wire_bytes(tree) == 4 * 2 * k_b * 8
    # the scalar-d overload keeps gossip_wire_bytes's single-buffer model
    assert eng.wire_bytes(165, n_agents=4) == 4 * 1 * k_b * 8

    # model-sharded layout: local() runs per shard, each pads its own window
    mesh = SimpleNamespace(shape={"data": 4, "model": 2})
    eng2 = CommRound(compressor=comp, mixer=packed_mixer(), mesh=mesh,
                     leaf_specs={"b": P("data", None),
                                 "w": P("data", "model")},
                     agent_axes=("data",))
    assert eng2.wire_bytes(tree) == 4 * 3 * k_b * 8  # w: 2 shards, b: 1


def test_ring_weights_n2_single_band():
    """n=2 ring: both shifts deliver the same agent; the executor must fold
    the whole neighbor weight into one band (regression: w_self*x + 2*w01*nb
    double-counted the neighbor and the circulant check hid it by
    overwriting ref[0,1])."""
    from repro.core.gossip import _ring_weights
    w2 = np.array([[0.5, 0.5], [0.5, 0.5]])
    w_self, w_prev, w_next = _ring_weights(w2)
    assert (w_self, w_prev, w_next) == (0.5, 0.5, 0.0)
    # row sum of the executed update is w_self + w_prev + w_next == 1
    assert w_self + w_prev + w_next == pytest.approx(1.0)
    # the accumulate-style check is honest: asymmetric 2x2 is not a ring band
    with pytest.raises(ValueError):
        _ring_weights(np.array([[0.6, 0.4], [0.3, 0.7]]))
    with pytest.raises(ValueError):
        _ring_weights(np.array([[1.0]]))  # n=1: no ring


def test_compress_stacked_per_agent_rows():
    """Each agent's row is compressed independently (k per row, not global)."""
    comp = make_compressor("top_k", frac=0.5)
    tree = {"w": jnp.asarray([[1.0, -2.0, 0.5, 3.0],
                              [10.0, 0.1, -0.2, 0.05]])}
    out = compress_stacked(comp, jax.random.PRNGKey(0), tree)["w"]
    # frac=0.5 of 4 -> 2 kept per row
    assert int((out[0] != 0).sum()) == 2
    assert int((out[1] != 0).sum()) == 2
    np.testing.assert_allclose(np.asarray(out[0]), [0, -2.0, 0, 3.0])
    np.testing.assert_allclose(np.asarray(out[1]), [10.0, 0, -0.2, 0])
