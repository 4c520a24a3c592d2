"""Directed-graph push-sum subsystem (core.push_sum + the engine's
``*_ps`` rounds + the dp-csgp registration).

* De-bias law: ``x / xw`` with weights exactly 1 is IEEE bit-identity --
  the exact-reduction lemma behind the parity test.
* Parity (acceptance): at period 1 with a symmetric doubly-stochastic
  table, dp-csgp is trajectory-identical to porter-dp (state and every
  metric except ``wire_bytes``, which additionally accounts the weight
  plane).
* Engine: the push-sum weight recursion matches a numpy mirror of the
  exact-EF recursion; the plain packed all-gather mixer (no weight slot)
  is rejected with a actionable error; push-sum wire accounting adds
  exactly 4 bytes per shipped buffer set on measured AND model paths.
* Facade: directed schedules reject doubly-stochastic algorithms; dp-csgp
  accepts them; mid-period checkpoint/resume restores the weight plane
  and step counter; a directed-churn schedule trains under chunking with
  one executable per chunk size.
* Subprocess (8 host devices): dense and ring push-sum executors agree
  with the numpy push-sum reference on static directed graphs (atol
  1e-5); the codec executor transports the weight increment exactly
  (``cw == dw`` bit-exact); the lowered dp-csgp step HLO contains exactly
  the same collectives as porter-dp's -- the weight plane rides inside
  existing collectives, never adds one.
"""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ExperimentSpec, algorithm_info, build, build_engine,
                       resolve_schedule)
from repro.core import mixing as MX
from repro.core import push_sum as PS
from repro.core.comm_round import CommRound
from repro.core.compression import make_compressor
from repro.data import minibatch_source
from repro.launch.runtime import make_runner

N, D, M, B = 4, 16, 32, 3


def _loss_fn(params, batch):
    f, l = batch
    f, l = jnp.atleast_2d(f), jnp.atleast_1d(l)
    logits = f @ params["w"] + params["b"]
    return jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))


def _problem(seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=D)
    f = rng.normal(size=(N, M, D)).astype(np.float32)
    l = (f @ w_true > 0).astype(np.float32)
    params0 = {"w": jnp.zeros(D), "b": jnp.zeros(())}
    return params0, minibatch_source(f, l, B)


def _spec(name, **kw):
    base = dict(algo=name, n_agents=N, topology="ring", compressor="top_k",
                frac=0.25, eta=0.1, tau=5.0, sigma_p=0.01)
    base.update(kw)
    return ExperimentSpec(**base)


def _per_step_loop(algo, source, state, key, steps, start=0):
    step = jax.jit(algo.step)
    traj = []
    for t in range(start, start + steps):
        kb, ks = jax.random.split(jax.random.fold_in(key, t))
        state, m = step(state, source(kb, jnp.asarray(t, jnp.int32)), ks)
        traj.append(m)
    return state, traj


# ---------------------------------------------------------------------------
# de-bias law
# ---------------------------------------------------------------------------

def test_debias_unit_weights_is_bit_identity():
    rng = np.random.default_rng(0)
    x = {"w": jnp.asarray(rng.normal(size=(N, 5)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(N,)), jnp.float32)}
    z = PS.debias(x, jnp.ones((N,), jnp.float32))
    for la, lb in zip(jax.tree_util.tree_leaves(x),
                      jax.tree_util.tree_leaves(z)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_debias_divides_per_agent_and_floors_zero():
    x = {"w": jnp.ones((3, 4), jnp.float32)}
    xw = jnp.asarray([2.0, 0.5, 0.0], jnp.float32)
    z = PS.debias(x, xw)["w"]
    np.testing.assert_allclose(np.asarray(z[0]), 0.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(z[1]), 2.0, rtol=1e-6)
    # the zero weight is floored, not a division by zero
    assert np.all(np.isfinite(np.asarray(z[2])))


# ---------------------------------------------------------------------------
# registration + guards
# ---------------------------------------------------------------------------

def test_dp_csgp_registered_as_dp_decentralized():
    info = algorithm_info("dp-csgp")
    assert info.dp and info.decentralized and info.compressed


def test_directed_schedule_rejects_doubly_stochastic_algorithms():
    params0, _ = _problem()
    sched = "directed:one_way,rate=0.2,period=4"
    for name in ("porter-gc", "porter-dp", "beer"):
        with pytest.raises(ValueError, match="dp-csgp"):
            build(_spec(name, topology_schedule=sched), _loss_fn)
    algo = build(_spec("dp-csgp", topology_schedule=sched), _loss_fn)
    assert algo.schedule.is_directed
    state = algo.init(params0)
    assert state.xw.shape == (N,)
    np.testing.assert_array_equal(np.asarray(state.xw), np.ones(N))


def test_exchange_ps_rejects_mixer_without_weight_transport():
    class _NoPushMixer:
        time_varying = False
        wire_mode = "packed"

        def __call__(self, tree, t=None):
            return tree

    eng = CommRound(compressor=make_compressor("top_k", frac=0.25),
                    mixer=_NoPushMixer())
    y = {"w": jnp.ones((N, 8), jnp.float32)}
    q = jax.tree_util.tree_map(jnp.zeros_like, y)
    with pytest.raises(ValueError, match="weight-plane transport"):
        eng.exchange_ps(jax.random.PRNGKey(0), y, q,
                        jnp.ones((N,)), jnp.zeros((N,)))


# ---------------------------------------------------------------------------
# engine: weight recursion + byte accounting
# ---------------------------------------------------------------------------

def test_step_ps_weight_recursion_matches_numpy():
    """The exact-EF weight recursion composes to
    xw' = ((1-gamma) I + gamma W) xw -- pinned against plain numpy."""
    sched = MX.directed_churn_schedule(N, rate=0.3, period=4, skip=2, seed=0)
    spec = ExperimentSpec(algo="dp-csgp", n_agents=N, compressor="identity",
                          topology_schedule="directed:one_way", gamma=0.4,
                          tau=1.0)
    eng = build_engine(spec, schedule=sched)
    gamma = 0.4
    rng = np.random.default_rng(3)
    x = {"w": jnp.asarray(rng.normal(size=(N, 7)), jnp.float32)}
    q = jax.tree_util.tree_map(jnp.zeros_like, x)
    m = jax.tree_util.tree_map(jnp.zeros_like, x)
    v = jax.tree_util.tree_map(jnp.zeros_like, x)
    xw = jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32)
    qw = jnp.zeros((N,), jnp.float32)
    mw = jnp.zeros((N,), jnp.float32)
    mass0 = float(jnp.sum(xw))

    # numpy mirror of the same EF recursion (identity compressor)
    nx, nq, nm = (np.asarray(x["w"], np.float64), np.zeros((N, 7)),
                  np.zeros((N, 7)))
    nxw, nqw, nmw = np.asarray(xw, np.float64), np.zeros(N), np.zeros(N)

    key = jax.random.PRNGKey(0)
    for t in range(6):
        tj = jnp.asarray(t, jnp.int32)
        x2, q2, m2, xw2, qw2, mw2 = eng.step_ps(
            key, x, q, m, v, xw, qw, mw, gamma, 0.0, t=tj)
        x, q, m, xw, qw, mw = x2, q2, m2, xw2, qw2, mw2

        w_t = sched.ws[t % sched.period]
        c = nx - nq
        nq = nq + c
        nm = nm + w_t @ c
        nx = nx + gamma * (nm - nq)
        cw = nxw - nqw
        nqw = nqw + cw
        nmw = nmw + w_t @ cw
        nxw = nxw + gamma * (nmw - nqw)

    np.testing.assert_allclose(np.asarray(x["w"]), nx, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(xw), nxw, atol=1e-5, rtol=1e-5)
    # column stochasticity conserves the initial total weight mass exactly
    np.testing.assert_allclose(float(jnp.sum(xw)), mass0, atol=1e-4)
    assert np.all(np.asarray(xw) > 0)


def test_push_sum_wire_bytes_add_weight_plane():
    """push_sum=True adds 4 bytes per shipped buffer set -- identically on
    the measured and the model path (dense mode: n sets)."""
    spec = ExperimentSpec(algo="dp-csgp", n_agents=N, compressor="top_k",
                          frac=0.25, tau=1.0,
                          topology_schedule="directed:ring_skips,skip=2")
    eng = build_engine(spec)
    y = {"w": jnp.ones((N, 32), jnp.float32)}
    plain, plain_model = eng.wire_bytes(y), eng.wire_bytes_model(y)
    ps, ps_model = (eng.wire_bytes(y, push_sum=True),
                    eng.wire_bytes_model(y, push_sum=True))
    assert plain == plain_model and ps == ps_model
    assert ps - plain == 4.0 * N


# ---------------------------------------------------------------------------
# parity with porter-dp (the exact-reduction acceptance)
# ---------------------------------------------------------------------------

def test_dp_csgp_matches_porter_dp_on_doubly_stochastic_table():
    """Acceptance: with a symmetric doubly-stochastic W (period 1) the
    weight increments are identically zero, xw stays exactly 1, and
    dp-csgp reproduces porter-dp bit-for-bit (wire_bytes excepted: the
    push-sum round honestly accounts its weight plane)."""
    params0, source = _problem()
    ref = build(_spec("porter-dp"), _loss_fn)
    got = build(_spec("dp-csgp"), _loss_fn)
    assert got.gamma == ref.gamma
    ref_state, ref_traj = _per_step_loop(
        ref, source, ref.init(params0), jax.random.PRNGKey(7), 5)
    got_state, got_traj = _per_step_loop(
        got, source, got.init(params0), jax.random.PRNGKey(7), 5)
    # weight plane never moved (q_w inits to 1, so increments are 0)
    np.testing.assert_array_equal(np.asarray(got_state.xw), np.ones(N))
    np.testing.assert_array_equal(np.asarray(got_state.q_w), np.ones(N))
    for field in ("x", "v", "q_x", "q_v", "g_prev", "m_x", "m_v"):
        for rl, gl in zip(
                jax.tree_util.tree_leaves(getattr(ref_state, field)),
                jax.tree_util.tree_leaves(getattr(got_state, field))):
            np.testing.assert_array_equal(np.asarray(rl), np.asarray(gl),
                                          err_msg=field)
    for rm, gm in zip(ref_traj, got_traj):
        for k in rm:
            if k == "wire_bytes":
                assert float(gm[k]) > float(rm[k])  # + weight plane
                continue
            np.testing.assert_array_equal(np.asarray(rm[k]),
                                          np.asarray(gm[k]), err_msg=k)


def test_dp_csgp_directed_departs_from_unit_weights():
    """Anti-vacuity: on a genuinely one-way schedule the weight plane must
    actually move (else the parity test above proves nothing)."""
    params0, source = _problem()
    algo = build(_spec("dp-csgp",
                       topology_schedule="directed:one_way,rate=0.3,"
                                         "period=4,skip=2"), _loss_fn)
    state, _ = _per_step_loop(algo, source, algo.init(params0),
                              jax.random.PRNGKey(7), 6)
    xw = np.asarray(state.xw, np.float64)
    assert not np.allclose(xw, 1.0, atol=1e-6)
    np.testing.assert_allclose(xw.sum(), N, atol=1e-4)  # mass conserved
    assert np.all(xw > 0)


# ---------------------------------------------------------------------------
# chunked training + mid-period resume (runtime-facing contract)
# ---------------------------------------------------------------------------

def test_directed_churn_chunked_training_single_executable():
    params0, source = _problem()
    algo = build(_spec("dp-csgp", sigma_p=0.0,
                       topology_schedule="directed:one_way,rate=0.25,"
                                         "period=4"), _loss_fn)
    runner = make_runner(algo, source, 4)
    state = algo.init(params0)
    key = jax.random.PRNGKey(0)
    losses = []
    for start in (0, 4, 8):   # crosses the period boundary twice
        state, key, m = runner(state, key, start)
        losses.extend(np.asarray(m["loss"]).tolist())
    assert runner.cache_size() in (None, 1)
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert int(state.step) == 12


def test_resume_mid_period_restores_weight_plane(tmp_path):
    """The checkpointed step counter AND the (n,) weight planes must both
    survive a restart: round t's W_t and the de-bias denominators continue
    exactly where the crashed run stopped."""
    from repro.launch.checkpoint import restore_state, save_state

    sched_str = "directed:one_way,rate=0.3,period=3,skip=2"  # 4 rounds: mid
    params0, source = _problem()
    spec = _spec("dp-csgp", sigma_p=0.0, topology_schedule=sched_str)
    algo = build(spec, _loss_fn)

    ref_state, _ = _per_step_loop(algo, source, algo.init(params0),
                                  jax.random.PRNGKey(7), 8)

    state, _, _ = make_runner(algo, source, 4)(
        algo.init(params0), jax.random.PRNGKey(7), 0)
    assert not np.allclose(np.asarray(state.xw), 1.0, atol=1e-6)
    save_state(tmp_path, state, step=4,
               extra={"topology_schedule": sched_str})

    algo2 = build(spec, _loss_fn)
    restored = restore_state(tmp_path, like=algo2.init(params0))
    assert int(restored.step) == 4      # 4 mod 3 = 1: mid-window
    np.testing.assert_array_equal(np.asarray(restored.xw),
                                  np.asarray(state.xw))
    np.testing.assert_array_equal(np.asarray(restored.q_w),
                                  np.asarray(state.q_w))
    state2, _, _ = make_runner(algo2, source, 4)(
        restored, jax.random.PRNGKey(7), 4)
    for rl, gl in zip(jax.tree_util.tree_leaves(ref_state),
                      jax.tree_util.tree_leaves(state2)):
        np.testing.assert_allclose(np.asarray(gl), np.asarray(rl),
                                   atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# executors on a real device mesh (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.api import ExperimentSpec, build, build_engine
    from repro.core import mixing as MX

    n, d = 8, 24
    mesh = make_mesh((n,), ("data",))
    specs = {"w": P("data", None)}
    sh = NamedSharding(mesh, specs["w"])
    rng = np.random.default_rng(0)
    gamma = 0.4

    def np_push_sum(w, x0, xw0, rounds):
        # numpy mirror of the exact-EF push-sum recursion (identity
        # compressor): q += c; m += W c; x += gamma (m - q), same for xw
        x, q, m = x0.copy(), np.zeros_like(x0), np.zeros_like(x0)
        xw, qw, mw = xw0.copy(), np.zeros(n), np.zeros(n)
        for _ in range(rounds):
            c = x - q;   q = q + c;   m = m + w @ c
            x = x + gamma * (m - q)
            cw = xw - qw; qw = qw + cw; mw = mw + w @ cw
            xw = xw + gamma * (mw - qw)
        return x, xw

    x0 = rng.normal(size=(n, d)).astype(np.float32)
    xw0 = rng.uniform(0.5, 1.5, n).astype(np.float32)

    # acceptance: dense and ring push-sum executors vs the numpy
    # reference on static directed graphs, atol 1e-5.  skip=3 chords are
    # genuinely column-only stochastic (dense/packed executors); the
    # skip-0 directed ring is the circulant band the ppermute ring
    # executor supports.
    cases = (("dense", "directed:ring_skips,skip=3", "dense-ps-ok"),
             ("ring", "directed:ring_skips", "ring-ps-ok"))
    for mode, sched_str, marker in cases:
        spec = ExperimentSpec(algo="dp-csgp", n_agents=n,
                              compressor="identity", tau=1.0, gamma=gamma,
                              topology_schedule=sched_str, gossip_mode=mode)
        eng = build_engine(spec, mesh=mesh, leaf_specs=specs)
        sched = MX.directed_ring_schedule(
            n, skip=3 if "skip=3" in sched_str else 0)
        x = {"w": jax.device_put(jnp.asarray(x0), sh)}
        q = jax.tree_util.tree_map(jnp.zeros_like, x)
        m = jax.tree_util.tree_map(jnp.zeros_like, x)
        v = jax.tree_util.tree_map(jnp.zeros_like, x)
        xw = jnp.asarray(xw0)
        qw = jnp.zeros((n,), jnp.float32)
        mw = jnp.zeros((n,), jnp.float32)

        step = jax.jit(lambda k, x, q, m, v, xw, qw, mw, t, e=eng:
                       e.step_ps(k, x, q, m, v, xw, qw, mw, gamma, 0.0,
                                 t=t))
        key = jax.random.PRNGKey(0)
        for t in range(6):
            x, q, m, xw, qw, mw = step(key, x, q, m, v, xw, qw, mw,
                                       jnp.asarray(t, jnp.int32))
        want_x, want_xw = np_push_sum(sched.ws[0], x0.astype(np.float64),
                                      xw0.astype(np.float64), 6)
        np.testing.assert_allclose(np.asarray(x["w"]), want_x, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(xw), want_xw, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(float(jnp.sum(xw)), float(xw0.sum()),
                                   atol=1e-4)
        print(marker)

    # codec executor: the weight increment travels EXACTLY (bit-exact
    # f32 words on the wire), and its mix follows the round's band weights
    from repro.core import wire_formats as WF
    dd = 2 * WF.PACK_BLOCK
    spec_c = ExperimentSpec(algo="dp-csgp", n_agents=n,
                            compressor="block_top_k", frac=0.25, tau=1.0,
                            gamma=gamma, gossip_mode="ring",
                            wire="packed_bits",
                            topology_schedule="directed:ring_skips",
                            comm_backend="ref", interpret=True)
    eng_c = build_engine(spec_c, mesh=mesh, leaf_specs=specs)
    sched0 = MX.directed_ring_schedule(n, skip=0)
    y = {"w": jax.device_put(
        jnp.asarray(rng.normal(size=(n, dd)).astype(np.float32)), sh)}
    qz = jax.tree_util.tree_map(jnp.zeros_like, y)
    yw = jnp.asarray(rng.uniform(0.5, 1.5, n).astype(np.float32))
    qw = jnp.zeros((n,), jnp.float32)
    c, wc, cw, wcw = jax.jit(
        lambda k, a, b, e, f: eng_c.exchange_ps(
            k, a, b, e, f, t=jnp.asarray(0, jnp.int32)))(
        jax.random.PRNGKey(1), y, qz, yw, qw)
    np.testing.assert_array_equal(np.asarray(cw), np.asarray(yw))  # exact
    np.testing.assert_allclose(np.asarray(wcw),
                               sched0.ws[0] @ np.asarray(yw, np.float64),
                               atol=1e-5, rtol=1e-5)
    print("codec-ps-ok")

    # the weight plane adds no collectives: dp-csgp's lowered step has
    # exactly porter-dp's per-category collective counts on the same spec
    from repro.analysis.hlo import collective_counts
    params0 = {"w": jnp.zeros(dd)}

    def loss(p, b):
        return jnp.mean((p["w"] - b) ** 2)

    counts = {}
    for name in ("porter-dp", "dp-csgp"):
        spec_h = ExperimentSpec(algo=name, n_agents=n, topology="ring",
                                topology_weights="metropolis",
                                compressor="block_top_k", frac=0.25,
                                gossip_mode="ring", wire="packed_bits",
                                comm_backend="ref", interpret=True,
                                eta=0.1, tau=5.0, sigma_p=0.01)
        algo = build(spec_h, loss, mesh=mesh, agent_axes=("data",),
                     leaf_specs=specs)
        state = algo.init(params0, n_agents=n)
        batch = jnp.zeros((n, 1, dd))
        hlo = (jax.jit(algo.step)
               .lower(state, batch, jax.random.PRNGKey(0))
               .compile().as_text())
        counts[name] = collective_counts(hlo)
    assert counts["porter-dp"] == counts["dp-csgp"], counts
    assert sum(counts["dp-csgp"].values()) > 0, counts
    print("hlo-ps-ok")
""")


def test_push_sum_executors_and_hlo():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stderr[-3000:]
    for marker in ("dense-ps-ok", "ring-ps-ok", "codec-ps-ok", "hlo-ps-ok"):
        assert marker in res.stdout, (marker, res.stdout, res.stderr[-2000:])
