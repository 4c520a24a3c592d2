"""Fleet-mode oracle: the vectorized fleet executor vs. the per-device
engine.

The fleet subsystem (``core/fleet.py`` + ``ExperimentSpec(fleet=True)``)
simulates n >> devices agents as one leading vmapped axis.  Its contract,
pinned here:

* **Bit parity below the gate**: at ``n <= FLEET_DENSE_GATE`` the fleet
  mixer reuses the gossip module's schedule-table einsum verbatim, so
  every registered decentralized algorithm must produce *bit-identical*
  trajectories in fleet and per-device mode (same key stream).
* **COO parity above the gate**: the sparse scatter-add sweep agrees with
  its own densified table to f32 accumulation error, and the sparse
  builders reproduce ``make_topology``'s Metropolis weights exactly.
* **Runtime integration**: the chunked scan runner and mid-run checkpoint
  resume see fleet states as ordinary agent-stacked pytrees -- one
  executable per chunk size, bit-exact resume.
* **SPMD**: sharding the fleet axis over 8 host devices changes neither
  the results nor the compiled collective census vs. the per-device dense
  engine (subprocess case, HLO collective-count equality).
* **clip21 degeneracy**: at tau = inf the Clip21 EF clip is the identity
  on the residual, so clip21 must match porter-gc bit-for-bit.
"""

import collections
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ExperimentSpec, algorithm_info, build, build_engine,
                       list_algorithms, resolve_fleet_schedule,
                       resolve_fleet_topology)
from repro.core import (FLEET_DENSE_GATE, FleetSchedule, FleetTopology,
                        make_topology)
from repro.core.fleet import (fleet_er_schedule, fleet_rotating_schedule,
                              fleet_topology, make_fleet_mixer)
from repro.core.mixing import mixing_rate
from repro.data import dirichlet_partition, dirichlet_source
from repro.launch.checkpoint import latest_step, restore_state, save_state
from repro.launch.runtime import make_runner

D, B = 24, 6

DECENTRALIZED = sorted(a for a in list_algorithms()
                       if algorithm_info(a).decentralized)


def _loss_fn(params, batch):
    f, l = batch
    f, l = jnp.atleast_2d(f), jnp.atleast_1d(l)
    logits = f @ params["w"] + params["b"]
    return jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))


def _problem(n, seed=0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=D)
    f = rng.normal(size=(n, B, D)).astype(np.float32)
    l = (f @ w_true > 0).astype(np.float32)
    params0 = {"w": jnp.zeros(D), "b": jnp.zeros(())}
    return params0, (jnp.asarray(f), jnp.asarray(l))


def _spec(name, n, *, fleet, **over):
    kw = dict(algo=name, n_agents=n, topology="ring", compressor="top_k",
              frac=0.25, eta=0.1, tau=5.0,
              sigma_p=0.01 if algorithm_info(name).dp else 0.0,
              fleet=fleet)
    kw.update(over)
    return ExperimentSpec(**kw)


def _run(algo, params0, batch, steps, seed=0):
    """The runtime's key contract: round t's keys are a pure function of
    the absolute index, so fleet/per-device runs share the stream."""
    state = algo.init(params0)
    step = jax.jit(algo.step)
    key = jax.random.PRNGKey(seed)
    losses = []
    for t in range(steps):
        _, ks = jax.random.split(jax.random.fold_in(key, t))
        state, m = step(state, batch, ks)
        losses.append(m["loss"])
    return state, np.asarray(losses)


def _assert_tree_equal(a, b, *, exact, atol=1e-5, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if exact:
            np.testing.assert_array_equal(x, y, err_msg=msg)
        else:
            np.testing.assert_allclose(x, y, atol=atol, rtol=1e-5,
                                       err_msg=msg)


# ---------------------------------------------------------------------------
# Oracle parity: every decentralized algorithm, n = 4 and n = 8
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", DECENTRALIZED)
@pytest.mark.parametrize("n", [4, 8])
def test_fleet_matches_per_device_oracle(name, n):
    """fleet=True is bit-identical to the per-device engine below the
    dense gate (same einsum table), not merely atol-close."""
    params0, batch = _problem(n)
    states, traj = [], []
    for fleet in (False, True):
        algo = build(_spec(name, n, fleet=fleet), _loss_fn)
        st, losses = _run(algo, params0, batch, steps=10)
        states.append(st)
        traj.append(losses)
    np.testing.assert_allclose(traj[1], traj[0], atol=1e-5, rtol=1e-5)
    _assert_tree_equal(states[1], states[0], exact=True,
                       msg=f"{name} n={n}: fleet diverged from oracle")
    assert np.isfinite(traj[1]).all()


def test_fleet_schedule_matches_per_device_oracle():
    """Time-varying tables take the same fleet path (traced W_t gather)."""
    n, sched = 8, "rotate:ring/metropolis+exponential/metropolis"
    params0, batch = _problem(n)
    states = []
    for fleet in (False, True):
        algo = build(_spec("porter-gc", n, fleet=fleet,
                           topology_schedule=sched), _loss_fn)
        st, _ = _run(algo, params0, batch, steps=8)
        states.append(st)
    _assert_tree_equal(states[1], states[0], exact=True)


# ---------------------------------------------------------------------------
# clip21 degeneracy: tau = inf recovers porter-gc exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tau", [None, float("inf")])
def test_clip21_is_porter_gc_at_infinite_tau(tau):
    """With tau = inf the residual clip factor is 1, the EF estimate locks
    onto the raw gradient (where-branch, not a+1.0*(b-a)), and clip21 is
    bit-for-bit porter-gc with piecewise clipping."""
    n = 8
    params0, batch = _problem(n)
    ref = build(_spec("porter-gc", n, fleet=False, tau=float("inf"),
                      clip_mode="piecewise"), _loss_fn)
    got = build(_spec("clip21", n, fleet=False, tau=tau), _loss_fn)
    st_ref, tr_ref = _run(ref, params0, batch, steps=12)
    st_got, tr_got = _run(got, params0, batch, steps=12)
    np.testing.assert_array_equal(tr_got, tr_ref)
    _assert_tree_equal(st_got.base, st_ref, exact=True)
    # and the EF estimate tracked the raw gradient exactly
    last = build(_spec("clip21", n, fleet=False, tau=tau), _loss_fn)
    st = last.init(params0)
    key = jax.random.PRNGKey(0)
    _, ks = jax.random.split(jax.random.fold_in(key, 0))
    st, m = jax.jit(last.step)(st, batch, ks)
    assert float(m["clip_residual"]) == 0.0


def test_clip21_finite_tau_diverges_from_porter_gc():
    """Sanity: the equivalence is a tau=inf degeneracy, not an identity."""
    n = 4
    params0, batch = _problem(n)
    ref = build(_spec("porter-gc", n, fleet=False, tau=0.5,
                      clip_mode="piecewise"), _loss_fn)
    got = build(_spec("clip21", n, fleet=False, tau=0.5), _loss_fn)
    st_ref, _ = _run(ref, params0, batch, steps=6)
    st_got, _ = _run(got, params0, batch, steps=6)
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree_util.tree_leaves(st_got.base),
                             jax.tree_util.tree_leaves(st_ref))]
    assert max(diffs) > 0.0


# ---------------------------------------------------------------------------
# COO executor vs. its densified table; sparse builders vs. make_topology
# ---------------------------------------------------------------------------

def test_fleet_metropolis_matches_make_topology():
    top = fleet_topology("ring", 16, weights="metropolis")
    dense = make_topology("ring", 16, weights="metropolis")
    np.testing.assert_array_equal(np.asarray(top.densify()),
                                  np.asarray(dense.w))
    assert abs(top.alpha - mixing_rate(dense.w)) < 1e-8


def test_coo_apply_matches_dense_gate():
    """Force the COO scatter-add at small n and compare against the
    einsum path on the same FleetTopology."""
    top = fleet_topology("exponential", 32, weights="lazy")
    coo = make_fleet_mixer(top, dense_gate=0)
    ein = make_fleet_mixer(top)
    assert coo.wire_mode == ein.wire_mode == "dense"
    key = jax.random.PRNGKey(3)
    tree = {"a": jax.random.normal(key, (32, 5, 3)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (32, 7))}
    out_c, out_e = jax.jit(coo)(tree), jax.jit(ein)(tree)
    _assert_tree_equal(out_c, out_e, exact=False, atol=1e-6)
    # push-sum weight rider: exact on the weight plane
    w0 = jnp.ones((32,))
    (tc, wc) = coo.push(tree, w0)
    (te, we) = ein.push(tree, w0)
    np.testing.assert_allclose(np.asarray(wc), np.asarray(we), atol=1e-6)
    _assert_tree_equal(tc, te, exact=False, atol=1e-6)


def test_coo_schedule_apply_matches_densified():
    sched = fleet_er_schedule(40, period=3, degree=6, seed=1)
    coo = make_fleet_mixer(sched, dense_gate=0)
    assert coo.time_varying
    key = jax.random.PRNGKey(0)
    tree = {"x": jax.random.normal(key, (40, 9))}
    for t in range(4):
        w_t = np.asarray(sched.densify(t % sched.period))
        want = {"x": w_t @ np.asarray(tree["x"])}
        got = jax.jit(coo)(tree, t=jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(got["x"]), want["x"],
                                   atol=1e-5, rtol=1e-5)
    with pytest.raises(TypeError):
        coo(tree)  # time-varying mixers require the round index


def test_fleet_above_gate_trains():
    """End-to-end COO path: n = 512 > FLEET_DENSE_GATE, one executable,
    finite decreasing loss."""
    n = 512
    assert n > FLEET_DENSE_GATE
    params0, _ = _problem(4)
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=D)
    f = rng.normal(size=(n, B, D)).astype(np.float32)
    l = (f @ w_true > 0).astype(np.float32)
    batch = (jnp.asarray(f), jnp.asarray(l))
    algo = build(_spec("clip21", n, fleet=True), _loss_fn)
    assert isinstance(algo.topology, FleetTopology)
    _, losses = _run(algo, params0, batch, steps=8)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# Sparse builders: validation + spectral agreement above the gate
# ---------------------------------------------------------------------------

def test_fleet_topology_spectral_matches_dense():
    top = fleet_topology("ring", 300, weights="metropolis")
    w = np.asarray(top.densify())
    np.testing.assert_allclose(w.sum(0), 1.0, atol=1e-9)
    np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-9)
    assert abs(top.alpha - mixing_rate(jnp.asarray(w))) < 1e-6 * top.alpha
    assert 0.0 < top.spectral_gap < 1.0


def test_fleet_er_schedule_validates():
    sched = fleet_er_schedule(400, period=3, seed=2)
    assert isinstance(sched, FleetSchedule)
    assert sched.period == 3 and not sched.is_directed
    assert 0.0 < sched.joint_alpha < 1.0
    for t in range(sched.period):
        w = np.asarray(sched.densify(t))
        np.testing.assert_allclose(w.sum(0), 1.0, atol=1e-8)
        np.testing.assert_allclose(w.sum(1), 1.0, atol=1e-8)


def test_fleet_rotating_schedule_validates():
    sched = fleet_rotating_schedule(["ring", "exponential/lazy"], 300)
    assert sched.period == 2
    assert 0.0 < sched.alpha < 1.0


def test_fleet_topology_rejects_best_constant():
    with pytest.raises(ValueError):
        fleet_topology("ring", 400, weights="best_constant")


# ---------------------------------------------------------------------------
# Spec routing + rejections
# ---------------------------------------------------------------------------

def test_fleet_spec_rejections():
    with pytest.raises(ValueError, match="gossip_mode"):
        build(_spec("porter-gc", 8, fleet=True, gossip_mode="ring"),
              _loss_fn)
    with pytest.raises(ValueError, match="wire"):
        build(_spec("porter-gc", 8, fleet=True, wire="packed_bits"),
              _loss_fn)
    with pytest.raises(ValueError, match="push-sum"):
        build(_spec("dp-csgp", FLEET_DENSE_GATE + 1, fleet=True), _loss_fn)
    with pytest.raises(ValueError, match="column-stochastic"):
        build(_spec("porter-gc", 8, fleet=True,
                    topology_schedule="directed:one_way,rate=0.2,period=3"),
              _loss_fn)
    with pytest.raises(ValueError):
        resolve_fleet_schedule(_spec("porter-gc", 512, fleet=True,
                                     topology_schedule="dropout:rate=0.2"))


def test_fleet_resolution_below_gate_is_dense():
    spec = _spec("porter-gc", 8, fleet=True)
    top = resolve_fleet_topology(spec)
    assert not isinstance(top, FleetTopology)  # ordinary dense Topology
    eng = build_engine(spec)
    assert eng.mixer.budget.executor == "fleet"
    assert eng.mixer.n == 8


def test_fleet_resolution_above_gate_is_sparse():
    spec = _spec("porter-gc", 512, fleet=True)
    top = resolve_fleet_topology(spec)
    assert isinstance(top, FleetTopology)
    assert top.nnz < 512 * 64  # never materializes (n, n)


# ---------------------------------------------------------------------------
# Runtime integration: chunked scan + mid-run checkpoint resume
# ---------------------------------------------------------------------------

def test_fleet_chunked_runner_parity():
    """The scan-fused chunk runner reproduces the per-step loop on a fleet
    state -- uneven tail chunk, one executable."""
    from repro.data import minibatch_source
    n = 8
    params0, (f, l) = _problem(n)
    source = minibatch_source(np.asarray(f), np.asarray(l), 3)
    algo = build(_spec("clip21", n, fleet=True), _loss_fn)

    key = jax.random.PRNGKey(0)
    step = jax.jit(algo.step)
    st_loop = algo.init(params0)
    for t in range(7):
        kb, ks = jax.random.split(jax.random.fold_in(key, t))
        st_loop, _ = step(st_loop, source(kb, t), ks)

    runner = make_runner(algo, source, chunk=3, donate=False)
    st_run = algo.init(params0)
    st_run, _, _ = runner(st_run, key, start=0)    # t = 0..2
    st_run, _, _ = runner(st_run, key, start=3)    # t = 3..5
    st_run, _, _ = make_runner(algo, source, chunk=1,
                               donate=False)(st_run, key, start=6)
    _assert_tree_equal(st_run, st_loop, exact=False, atol=1e-5)
    assert runner.cache_size() in (None, 1)


def test_fleet_checkpoint_resume(tmp_path):
    """Mid-run save -> restore -> continue is bit-exact vs. uninterrupted
    (the fold_in key contract makes the stream restart-invariant)."""
    n = 8
    params0, batch = _problem(n)
    algo = build(_spec("clip21", n, fleet=True), _loss_fn)
    step = jax.jit(algo.step)
    key = jax.random.PRNGKey(1)

    def advance(st, t0, t1):
        for t in range(t0, t1):
            _, ks = jax.random.split(jax.random.fold_in(key, t))
            st, _ = step(st, batch, ks)
        return st

    st_full = advance(algo.init(params0), 0, 10)

    ckpt = str(tmp_path / "fleet_ckpt")
    st_half = advance(algo.init(params0), 0, 5)
    save_state(ckpt, st_half, step=5)
    assert latest_step(ckpt) == 5
    st_res = restore_state(ckpt, algo.init(params0))
    _assert_tree_equal(st_res, st_half, exact=True)
    st_res = advance(st_res, 5, 10)
    _assert_tree_equal(st_res, st_full, exact=True,
                       msg="resume diverged from uninterrupted run")


# ---------------------------------------------------------------------------
# Dirichlet fleet shards
# ---------------------------------------------------------------------------

def test_dirichlet_partition_shapes_and_determinism():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(240, 10)).astype(np.float32)
    ys = (xs[:, 0] > 0).astype(np.float32)
    fa, la = dirichlet_partition(xs, ys, n_agents=12, alpha=0.3, seed=7)
    fb, lb = dirichlet_partition(xs, ys, n_agents=12, alpha=0.3, seed=7)
    assert fa.shape == (12, 20, 10) and la.shape == (12, 20)
    np.testing.assert_array_equal(fa, fb)
    # heterogeneity: small alpha concentrates labels per agent
    fh, lh = dirichlet_partition(xs, ys, n_agents=12, alpha=0.05, seed=7)
    skew = np.mean(np.abs(lh.mean(axis=1) - ys.mean()))
    base = np.mean(np.abs(la.mean(axis=1) - ys.mean()))
    assert skew >= base


def test_dirichlet_source_feeds_fleet_training():
    rng = np.random.default_rng(1)
    xs = rng.normal(size=(512, D)).astype(np.float32)
    ys = (xs @ rng.normal(size=D) > 0).astype(np.float32)
    n = 8
    source = dirichlet_source(xs, ys, n_agents=n, batch=4, alpha=0.3)
    params0, _ = _problem(n)
    algo = build(_spec("subgrad-comp", n, fleet=True), _loss_fn)
    st = algo.init(params0)
    step = jax.jit(algo.step)
    key = jax.random.PRNGKey(0)
    for t in range(6):
        kb, ks = jax.random.split(jax.random.fold_in(key, t))
        st, m = step(st, source(kb, t), ks)
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# 8-device shard_map subprocess: parity + collective-count equality
# ---------------------------------------------------------------------------

SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import collections
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.api import ExperimentSpec, build
    from repro.analysis.hlo import collective_counts

    N, D, B = 8, 24, 4
    def loss_fn(params, batch):
        f, l = batch
        f, l = jnp.atleast_2d(f), jnp.atleast_1d(l)
        logits = f @ params["w"] + params["b"]
        return jnp.mean(jnp.log1p(jnp.exp(-(2 * l - 1) * logits)))

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=D)
    f = rng.normal(size=(N, B, D)).astype(np.float32)
    l = (f @ w_true > 0).astype(np.float32)
    params0 = {"w": jnp.zeros(D), "b": jnp.zeros(())}

    mesh = make_mesh((8,), ("data",))
    def shardings(tree):
        def spec(leaf):
            if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] == N:
                return NamedSharding(mesh, P("data",
                                             *([None] * (leaf.ndim - 1))))
            return NamedSharding(mesh, P())
        return jax.tree_util.tree_map(spec, tree)

    texts, finals = {}, {}
    for fleet in (False, True):
        spec = ExperimentSpec(algo="porter-gc", n_agents=N, topology="ring",
                              compressor="top_k", frac=0.25, eta=0.1,
                              tau=5.0, gossip_mode="dense", fleet=fleet)
        algo = build(spec, loss_fn)
        st = jax.device_put(algo.init(params0), shardings(algo.init(params0)))
        batch = (jax.device_put(jnp.asarray(f),
                                NamedSharding(mesh, P("data", None, None))),
                 jax.device_put(jnp.asarray(l),
                                NamedSharding(mesh, P("data", None))))
        key = jax.random.PRNGKey(0)
        step = jax.jit(algo.step)
        texts[fleet] = step.lower(st, batch, key).compile().as_text()
        for t in range(5):
            _, ks = jax.random.split(jax.random.fold_in(key, t))
            st, m = step(st, batch, ks)
        finals[fleet] = [np.asarray(x)
                         for x in jax.tree_util.tree_leaves(st)]

    for a, b in zip(finals[False], finals[True]):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    print("shard-parity-ok")

    ca, cb = collective_counts(texts[False]), collective_counts(texts[True])
    assert ca == cb, (ca, cb)
    assert sum(ca.values()) > 0  # the mesh really induced collectives
    print("census-equal-ok", sorted((k, v) for k, v in ca.items() if v))
""")


def test_fleet_shard_map_parity_and_census():
    """Under an 8-device agent mesh the fleet executor's compiled program
    has the same per-category collective counts as the per-device dense
    engine, and the sharded runs agree."""
    import os
    r = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=str(__import__("pathlib").Path(
                           __file__).resolve().parents[1]))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "shard-parity-ok" in r.stdout
    assert "census-equal-ok" in r.stdout


# ---------------------------------------------------------------------------
# Analyzer census: fleet mixing is device-local math, zero collectives
# ---------------------------------------------------------------------------

def test_fleet_census_zero_collectives():
    """The analyzer's fleet cases (einsum below the gate, COO above) must
    compile to programs with no collective ops at all in the unmeshed
    harness -- the fleet budget's empty per_leaf table makes any
    collective an unbudgeted violation."""
    from repro.analysis.sweep import census_matrix, run_census_case
    fleet_cases = [c for c in census_matrix() if "/fleet/" in c.label]
    assert len(fleet_cases) >= 3  # porter-gc, clip21, subgrad-comp@COO
    assert any(c.spec.n_agents > FLEET_DENSE_GATE for c in fleet_cases)
    for case in fleet_cases:
        assert not case.needs_mesh
        rec = run_census_case(case, mesh=None)
        assert rec["ok"], rec
        census = rec["census"]
        assert sum(census["counts"].values()) == 0, rec
        assert sum(census["spmd_counts"].values()) == 0, rec
        assert census["executor"] == "fleet"
