"""Bit-packed wire formats: kernel/reference parity, codec executors, and
comm/compute overlap.

* Pack -> unpack round-trip parity between the fused pallas kernels
  (interpret mode) and the jnp reference codecs in repro.core.wire_formats,
  on odd (non-window-aligned) sizes and bf16 planes.  Both sides implement
  the SAME bisection-threshold selection, so parity is bit-level, asserted
  at the issue's atol 1e-5.
* measured buffer nbytes == the registered layout constants for every d
  (the executor / kernel / byte-model drift-bug class).
* Codec gossip executors (ring ppermute of packed buffers, packed
  all-gather) against the dense-mixer-on-oracle-roundtrip, including n=2
  ring band folding and a model-sharded leaf -- in a subprocess with 8
  host devices (see test_distributed_gossip.py).
* CommRound(overlap=True): bit-exact to the sequential order for all
  eight registered algorithms, and (in the subprocess) the lowered HLO of
  an overlapped PORTER step contains exactly the same collectives as the
  sequential one.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build, list_algorithms
from repro.core import wire_formats as WF
from repro.kernels import ops

ODD_SIZES = (5, 2047, 2049, 20_001)
K = 512          # frac=0.25 of PACK_BLOCK
LEVELS = 7       # 4-bit code words (sign + 3-bit magnitude)


def _rows(d, seed=0, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,), dtype)
    return x, WF.to_windows(x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# pallas-interpret vs jnp reference codec parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", ODD_SIZES)
def test_topk_pack_parity_odd_shapes(d):
    x, rows = _rows(d)
    vals_r, idx_r = WF.topk_pack_ref(rows, K)
    vals_p, idx_p = ops.wire_topk_pack(rows, K, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx_p, np.int32),
                                  np.asarray(idx_r, np.int32))
    np.testing.assert_allclose(np.asarray(vals_p, np.float32),
                               np.asarray(vals_r, np.float32), atol=1e-5)
    dense_r = WF.topk_unpack_ref(vals_r, idx_r)
    dense_p = ops.wire_topk_unpack(vals_p, idx_p, interpret=True)
    np.testing.assert_allclose(np.asarray(dense_p), np.asarray(dense_r),
                               atol=1e-5)
    # round trip: kept entries survive up to bf16 value rounding, the rest
    # are exactly zero; the padded tail (window beyond d) stays zero
    back = WF.from_windows(dense_r, d, x.shape)
    a = np.abs(np.asarray(x))
    kept = np.asarray(back) != 0
    assert kept.sum() <= min(K * rows.shape[0], d)
    np.testing.assert_allclose(np.asarray(back)[kept],
                               np.asarray(x)[kept], rtol=2 ** -8)


def test_topk_pack_parity_bf16_plane():
    # bf16 parameter planes enter the codec through the f32 staging cast
    # (gossip._pack_local) and leave through unpack(dtype=bf16)
    x, rows = _rows(4097, seed=3, dtype=jnp.bfloat16)
    vals_r, idx_r = WF.topk_pack_ref(rows, K)
    vals_p, idx_p = ops.wire_topk_pack(rows, K, interpret=True)
    np.testing.assert_array_equal(np.asarray(idx_p, np.int32),
                                  np.asarray(idx_r, np.int32))
    out_r = WF.topk_unpack_ref(vals_r, idx_r, dtype=jnp.bfloat16)
    out_p = ops.wire_topk_unpack(vals_p, idx_p,
                                 interpret=True).astype(jnp.bfloat16)
    assert out_r.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out_p, np.float32), np.asarray(out_r, np.float32))


@pytest.mark.parametrize("d", ODD_SIZES)
def test_qsgd_pack_parity_odd_shapes(d):
    _, rows = _rows(d, seed=1)
    key = jax.random.PRNGKey(42)
    word_r, scale_r = WF.qsgd_pack_ref(key, rows, LEVELS)
    word_p, scale_p = ops.wire_qsgd_pack(rows, key, LEVELS, interpret=True)
    # identical stochastic rounding noise -> bit-identical code words
    np.testing.assert_array_equal(np.asarray(word_p), np.asarray(word_r))
    np.testing.assert_allclose(np.asarray(scale_p), np.asarray(scale_r),
                               atol=1e-5)
    dense_r = WF.qsgd_unpack_ref(word_r, scale_r, LEVELS, jnp.float32)
    dense_p = ops.wire_qsgd_unpack(word_p, scale_p, LEVELS, interpret=True)
    np.testing.assert_allclose(np.asarray(dense_p), np.asarray(dense_r),
                               atol=1e-5)


def test_qsgd_roundtrip_contract():
    # Definition 3 per window: ||C(x) - x||^2 <= (1 - 1/(1+omega)) ||x||^2
    # with omega = min(sqrt(B)/s, B/s^2); sampled over keys
    d = 3 * WF.PACK_BLOCK
    x, rows = _rows(d, seed=2)
    omega = WF.qsgd_window_omega(LEVELS)
    bound = 1.0 - 1.0 / (1.0 + omega)
    errs = []
    for s in range(5):
        word, scale = WF.qsgd_pack_ref(jax.random.PRNGKey(s), rows, LEVELS)
        back = WF.qsgd_unpack_ref(word, scale, LEVELS, jnp.float32)
        errs.append(float(jnp.sum((back - rows) ** 2) / jnp.sum(rows ** 2)))
    assert np.mean(errs) <= bound + 1e-3, (np.mean(errs), bound)


# ---------------------------------------------------------------------------
# layout constants cannot drift from the shipped buffers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", ODD_SIZES + (WF.PACK_BLOCK, 8 * WF.PACK_BLOCK))
def test_measured_nbytes_match_model(d):
    topk = WF.make_wire_format("block_top_k", frac=0.25)
    qsgd = WF.make_wire_format("qsgd", levels=LEVELS)
    for fmt in (topk, qsgd):
        assert WF.measured_pack_nbytes(fmt, d) == fmt.buffer_bytes(d), fmt.name


def test_wire_format_registry():
    # one shared constants module: every registered format resolves, and
    # qsgd is registered alongside PACK_BLOCK (the former footnote gap)
    assert WF.WIRE_FORMATS == ("topk_bits", "qsgd_bits")
    assert WF.make_wire_format("top_k", frac=0.1).name == "topk_bits"
    assert WF.make_wire_format("qsgd", levels=15).name == "qsgd_bits"
    with pytest.raises(ValueError, match="no registered"):
        WF.make_wire_format("random_k", frac=0.1)


# ---------------------------------------------------------------------------
# overlap is bit-exact for every registered algorithm
# ---------------------------------------------------------------------------

def _loss_fn(params, batch):
    f, l = batch
    f, l = jnp.atleast_2d(f), jnp.atleast_1d(l)
    logits = f @ params["w"] + params["b"]
    return jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))


@pytest.mark.parametrize("name", sorted(list_algorithms()))
def test_overlap_bitexact_all_algorithms(name):
    n, d, m, b = 4, 16, 32, 3
    rng = np.random.default_rng(0)
    f = rng.normal(size=(n, m, d)).astype(np.float32)
    l = (f @ rng.normal(size=d) > 0).astype(np.float32)
    params0 = {"w": jnp.zeros(d), "b": jnp.zeros(())}
    spec = ExperimentSpec(
        algo=name, n_agents=n, topology="ring", compressor="top_k",
        frac=0.25, eta=0.1, tau=5.0,
        sigma_p=0.01 if name in ("porter-dp", "dp-sgd", "soteriafl") else 0.0)

    def run(overlap):
        algo = build(spec.replace(overlap=overlap), _loss_fn)
        state = algo.init(params0)
        step = jax.jit(algo.step)
        key = jax.random.PRNGKey(7)
        for t in range(3):
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            idx = jax.random.randint(kb, (n, b), 0, m)
            batch = (jnp.asarray(f)[jnp.arange(n)[:, None], idx],
                     jnp.asarray(l)[jnp.arange(n)[:, None], idx])
            state, metrics = step(state, batch, ks)
        return state, metrics

    st_seq, m_seq = run(False)
    st_ovl, m_ovl = run(True)
    for a, b_ in zip(jax.tree_util.tree_leaves(st_seq),
                     jax.tree_util.tree_leaves(st_ovl)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    for k in m_seq:
        np.testing.assert_array_equal(np.asarray(m_seq[k]),
                                      np.asarray(m_ovl[k]))


# ---------------------------------------------------------------------------
# codec executors on a real device mesh (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.api import ExperimentSpec, build, build_engine
    from repro.core import wire_formats as WF
    from repro.core.gossip import make_dense_mixer
    from repro.core.mixing import make_topology

    mesh = make_mesh((4, 2), ("data", "model"))
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (4, 6, 8)),
            "b": jax.random.normal(key, (4, 10))}
    specs = {"a": P("data", None, "model"), "b": P("data", None)}
    sh = {k: NamedSharding(mesh, specs[k]) for k in specs}
    y = {k: jax.device_put(tree[k], sh[k]) for k in tree}
    q = jax.tree_util.tree_map(jnp.zeros_like, y)
    top = make_topology("ring", 4, weights="metropolis")

    def oracle_c(codec, tree):
        # shard-local pack -> unpack round trip, the codec's own law
        def per_shard(tt):
            def leaf(l):
                flat = l.reshape(l.shape[0], -1).astype(jnp.float32)
                def one(v):
                    rows = WF.to_windows(v)
                    return WF.from_windows(
                        codec.unpack(*codec.pack(None, rows)),
                        v.shape[0], v.shape)
                return jax.vmap(one)(flat).reshape(l.shape)
            return jax.tree_util.tree_map(leaf, tt)
        f = jax.shard_map(per_shard, mesh=mesh, in_specs=(specs,),
                          out_specs=specs, check_vma=False)
        return jax.jit(f)(tree)

    codec = WF.make_wire_format("block_top_k", frac=0.25)
    want_c = oracle_c(codec, y)
    want_wc = make_dense_mixer(top.w)(
        jax.tree_util.tree_map(np.asarray, want_c))

    for mode, marker in (("ring", "ring-codec-ok"),
                         ("packed", "packed-codec-ok")):
        spec = ExperimentSpec(n_agents=4, topology="ring",
                              topology_weights="metropolis",
                              compressor="block_top_k", frac=0.25,
                              gossip_mode=mode, wire="packed_bits",
                              comm_backend="ref", interpret=True)
        eng = build_engine(spec, mesh=mesh, leaf_specs=specs)
        c, wc = jax.jit(lambda k, a, b, e=eng: e.exchange(k, a, b))(key, y, q)
        for k in tree:
            np.testing.assert_allclose(np.asarray(c[k]),
                                       np.asarray(want_c[k]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(wc[k]),
                                       np.asarray(want_wc[k]),
                                       rtol=1e-4, atol=1e-5)
        print(marker)

    # qsgd codec: stochastic, so pin same-key determinism + the m=Wq law
    # (wc must equal W @ c for the very same shipped buffers)
    spec_q = ExperimentSpec(n_agents=4, topology="ring",
                            topology_weights="metropolis",
                            compressor="qsgd",
                            compressor_kwargs={"levels": 7},
                            gossip_mode="ring", wire="packed_bits",
                            comm_backend="ref", interpret=True)
    eng_q = build_engine(spec_q, mesh=mesh, leaf_specs=specs)
    ex = jax.jit(lambda k, a, b: eng_q.exchange(k, a, b))
    c1, wc1 = ex(key, y, q)
    c2, wc2 = ex(key, y, q)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(c1[k]), np.asarray(c2[k]))
    want = make_dense_mixer(top.w)(jax.tree_util.tree_map(np.asarray, c1))
    for k in tree:
        np.testing.assert_allclose(np.asarray(wc1[k]), np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-5)
    print("qsgd-codec-ok")

    # n=2 ring folds both bands onto the one live neighbor -- the codec
    # executor must apply the neighbor's unpacked buffers exactly once
    mesh2 = make_mesh((2,), ("data",))
    top2 = make_topology("ring", 2, weights="metropolis")
    specs2 = {"a": P("data", None, None), "b": P("data", None)}
    sh2 = {k: NamedSharding(mesh2, specs2[k]) for k in specs2}
    tree2 = {"a": jax.random.normal(key, (2, 5, 3)),
             "b": jax.random.normal(key, (2, 7))}
    y2 = {k: jax.device_put(tree2[k], sh2[k]) for k in tree2}
    q2 = jax.tree_util.tree_map(jnp.zeros_like, y2)
    spec2 = ExperimentSpec(n_agents=2, topology="ring",
                           topology_weights="metropolis",
                           compressor="block_top_k", frac=0.25,
                           gossip_mode="ring", wire="packed_bits",
                           comm_backend="ref", interpret=True)
    eng2 = build_engine(spec2, mesh=mesh2, leaf_specs=specs2)
    c2t, wc2t = jax.jit(lambda k, a, b: eng2.exchange(k, a, b))(key, y2, q2)

    def oracle2(tt):
        def leaf(l):
            flat = l.reshape(l.shape[0], -1).astype(jnp.float32)
            def one(v):
                rows = WF.to_windows(v)
                return WF.from_windows(
                    codec.unpack(*codec.pack(None, rows)),
                    v.shape[0], v.shape)
            return jax.vmap(one)(flat).reshape(l.shape)
        return jax.tree_util.tree_map(leaf, tt)
    want_c2 = oracle2(tree2)
    want_wc2 = make_dense_mixer(top2.w)(
        jax.tree_util.tree_map(np.asarray, want_c2))
    for k in tree2:
        np.testing.assert_allclose(np.asarray(c2t[k]),
                                   np.asarray(want_c2[k]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(wc2t[k]),
                                   np.asarray(want_wc2[k]),
                                   rtol=1e-5, atol=1e-6)
    print("ring2-codec-ok")

    # overlap introduces no extra collectives: the lowered PORTER step has
    # identical per-category collective counts with overlap on and off
    from repro.analysis.hlo import collective_counts
    d = 2 * WF.PACK_BLOCK
    params0 = {"w": jnp.zeros(d)}
    pspecs = {"w": P("data", None)}

    def loss(p, b):
        return jnp.mean((p["w"] - b) ** 2)

    counts = {}
    for ovl in (False, True):
        spec_o = ExperimentSpec(algo="porter-gc", n_agents=4,
                                topology="ring",
                                topology_weights="metropolis",
                                compressor="block_top_k", frac=0.25,
                                gossip_mode="ring", wire="packed_bits",
                                comm_backend="ref", interpret=True,
                                eta=0.1, overlap=ovl)
        algo = build(spec_o, loss, mesh=mesh2, agent_axes=("data",),
                     leaf_specs=pspecs)
        state = algo.init(params0, n_agents=2)
        batch = jnp.zeros((2, 1, d))
        hlo = (jax.jit(algo.step)
               .lower(state, batch, jax.random.PRNGKey(0))
               .compile().as_text())
        counts[ovl] = collective_counts(hlo)
    assert counts[False] == counts[True], counts
    assert sum(counts[True].values()) > 0, counts
    print("hlo-overlap-ok")
""")


def test_codec_executors_and_overlap_hlo():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("ring-codec-ok", "packed-codec-ok", "qsgd-codec-ok",
                   "ring2-codec-ok", "hlo-overlap-ok"):
        assert marker in res.stdout, (marker, res.stdout, res.stderr[-2000:])
