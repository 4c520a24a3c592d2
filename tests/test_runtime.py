"""Chunked runtime contract (repro.launch.runtime).

* Parity: with an identical key stream, the scan-fused chunk runner must
  reproduce the per-step Python loop -- same final state, same metrics
  trajectory (allclose, atol 1e-5) -- for EVERY registered algorithm,
  including uneven tail chunks.
* Donation: the compiled runner actually donates the state input (buffers
  aliased in the executable, the argument invalidated after the call).
* One executable per chunk size: the chunk offset is traced, not static.
* BatchSource shapes for the model-zoo families + the on-device
  minibatch source.
* The checkpoint-manifest privacy accounting used by train.py --resume.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, build, list_algorithms
from repro.data import batch_source, minibatch_source
from repro.launch.runtime import make_runner, run_chunked

N, D, M, B = 4, 16, 32, 3
STEPS, CHUNK = 7, 3  # deliberately uneven: chunks of 3, 3, 1


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


def _spec(name):
    kw = dict(algo=name, n_agents=N, topology="ring", compressor="top_k",
              frac=0.25, eta=0.1, tau=5.0,
              sigma_p=0.01 if name in ("porter-dp", "dp-sgd", "soteriafl")
              else 0.0)
    return ExperimentSpec(**kw)


def _per_step_loop(algo, source, state, key, steps, start=0):
    """The per-step loop, with the runtime's exact key contract: round t's
    keys are split(fold_in(base, t)) -- a pure function of the absolute
    index, so chunking and restarts cannot change the stream."""
    step = jax.jit(algo.step)
    traj = []
    for t in range(start, start + steps):
        kb, ks = jax.random.split(jax.random.fold_in(key, t))
        state, m = step(state, source(kb, jnp.asarray(t, jnp.int32)), ks)
        traj.append(m)
    return state, traj


@pytest.mark.parametrize("name", sorted(list_algorithms()))
def test_chunked_runner_matches_per_step_loop(name):
    params0, source = _problem()
    algo = build(_spec(name), _loss_fn)

    ref_state, ref_traj = _per_step_loop(
        algo, source, algo.init(params0), jax.random.PRNGKey(7), STEPS)

    chunks = []
    state, _ = run_chunked(
        algo, source, algo.init(params0), jax.random.PRNGKey(7), STEPS,
        chunk=CHUNK, on_chunk=lambda t0, t1, st, m: chunks.append(m))

    assert sum(len(next(iter(m.values()))) for m in chunks) == STEPS
    for k in ref_traj[0]:
        got = np.concatenate([np.atleast_1d(np.asarray(m[k]))
                              for m in chunks])
        want = np.asarray([r[k] for r in ref_traj])
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5,
                                   err_msg=f"metric {k!r} diverged")
    for ref_leaf, got_leaf in zip(jax.tree_util.tree_leaves(ref_state),
                                  jax.tree_util.tree_leaves(state)):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(ref_leaf),
                                   atol=1e-5, rtol=1e-5)


def test_resume_continues_the_key_stream():
    """A restarted leg (fresh base-key object, later start) must continue
    the uninterrupted stream -- NOT replay the keys (and hence DP noise)
    rounds 0..k already consumed."""
    params0, source = _problem()
    algo = build(_spec("porter-dp"), _loss_fn)

    ref_state, ref_traj = _per_step_loop(
        algo, source, algo.init(params0), jax.random.PRNGKey(7), 8)

    runner = make_runner(algo, source, 4)
    state, _, m_a = runner(algo.init(params0), jax.random.PRNGKey(7), 0)
    # simulate a process restart: same seed, new key object, start=4
    state, _, m_b = runner(state, jax.random.PRNGKey(7), 4)
    # leg 2 must differ from leg 1 (no replay) and match the reference
    assert not np.allclose(np.asarray(m_a["loss"]), np.asarray(m_b["loss"]))
    np.testing.assert_allclose(
        np.concatenate([np.asarray(m_a["loss"]), np.asarray(m_b["loss"])]),
        np.asarray([r["loss"] for r in ref_traj]), atol=1e-5, rtol=1e-5)
    for ref_leaf, got_leaf in zip(jax.tree_util.tree_leaves(ref_state),
                                  jax.tree_util.tree_leaves(state)):
        np.testing.assert_allclose(np.asarray(got_leaf),
                                   np.asarray(ref_leaf),
                                   atol=1e-5, rtol=1e-5)


def test_runner_donates_state():
    from repro.analysis.hlo import donation_hlo_report

    params0, source = _problem()
    algo = build(_spec("porter-gc"), _loss_fn)
    runner = make_runner(algo, source, CHUNK)

    # the compiled program aliases every state leaf input to an output
    state_shapes = jax.eval_shape(lambda p: algo.init(p), params0)
    hlo = runner.lower(state_shapes).as_text()
    report = donation_hlo_report(
        hlo, len(jax.tree_util.tree_leaves(state_shapes)))
    assert report.ok, report.violations

    # and the call-site argument is actually consumed
    state = algo.init(params0)
    new_state, _, _ = runner(state, jax.random.PRNGKey(0), 0)
    # init aliases leaves (q_x is x, ...), so probe via the returned state
    # of a second call: its input is all-distinct buffers
    final, _, _ = runner(new_state, jax.random.PRNGKey(1), CHUNK)
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(new_state))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(final))


def test_runner_donate_false_keeps_state():
    params0, source = _problem()
    algo = build(_spec("porter-gc"), _loss_fn)
    runner = make_runner(algo, source, CHUNK, donate=False)
    state = algo.init(params0)
    runner(state, jax.random.PRNGKey(0), 0)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(state))


def test_one_executable_per_chunk_size():
    params0, source = _problem()
    algo = build(_spec("choco"), _loss_fn)
    runner = make_runner(algo, source, CHUNK)
    state = algo.init(params0)
    key = jax.random.PRNGKey(0)
    for start in (0, CHUNK, 2 * CHUNK):  # different offsets, one program
        state, key, _ = runner(state, key, start)
    assert runner.cache_size() in (None, 1)


def test_donation_never_consumes_caller_params():
    """Server/client inits used to adopt the caller's params buffers into
    state.x; a donated chunk then deleted params0 out from under the next
    run (benchmarks/run.py reuses one params0 across algorithms)."""
    params0, source = _problem()
    for name in ("soteriafl", "dp-sgd", "porter-gc", "choco", "dsgd"):
        algo = build(_spec(name), _loss_fn)
        make_runner(algo, source, 2)(algo.init(params0),
                                     jax.random.PRNGKey(0))
        assert not any(l.is_deleted()
                       for l in jax.tree_util.tree_leaves(params0)), name


def test_aliased_init_is_donatable():
    """porter_init aliases x/q_x/m_x and the zero buffers; the runner must
    still be callable with donation on the *initial* state."""
    params0, source = _problem()
    algo = build(_spec("porter-gc"), _loss_fn)
    state = algo.init(params0)
    leaves = jax.tree_util.tree_leaves(state)
    assert len({id(l) for l in leaves}) < len(leaves)  # init does alias
    out, _, _ = make_runner(algo, source, 2)(state, jax.random.PRNGKey(0))
    assert np.isfinite(float(jax.tree_util.tree_leaves(out)[0].sum()))


# ---------------------------------------------------------------------------
# batch sources
# ---------------------------------------------------------------------------

def test_minibatch_source_on_device_sampling():
    params0, source = _problem()
    key = jax.random.PRNGKey(3)
    xb, yb = source(key, jnp.asarray(0))
    assert xb.shape == (N, B, D) and yb.shape == (N, B)
    # deterministic in the key
    xb2, _ = source(key, jnp.asarray(9))
    np.testing.assert_array_equal(np.asarray(xb), np.asarray(xb2))
    # jit-traceable (the whole point: it runs inside the compiled chunk)
    jitted = jax.jit(source)
    xb3, _ = jitted(key, jnp.asarray(0))
    np.testing.assert_array_equal(np.asarray(xb), np.asarray(xb3))


@pytest.mark.parametrize("arch,keys", [
    ("tinyllama-1.1b", {"tokens"}),
    ("paligemma-3b", {"tokens", "patches"}),
    ("seamless-m4t-medium", {"frames", "tokens"}),
])
def test_batch_source_families(arch, keys):
    """Family-aware synthesis matches the layout train.py always fed the
    loss; checked abstractly (eval_shape) so no model compute runs."""
    from repro.configs import get_smoke
    cfg = get_smoke(arch)
    source = batch_source(cfg, n_agents=2, batch=3, seq=32)
    shapes = jax.eval_shape(source, jax.ShapeDtypeStruct((2,), jnp.uint32),
                            jax.ShapeDtypeStruct((), jnp.int32))
    assert set(shapes) == keys
    for k, s in shapes.items():
        assert s.shape[:2] == (2, 3), (k, s.shape)
    if "patches" in shapes:
        assert shapes["tokens"].shape[2] == 32 - cfg.n_prefix


# ---------------------------------------------------------------------------
# privacy accounting across resume (train.py + checkpoint manifest)
# ---------------------------------------------------------------------------

def _train_args(steps=40, tau=1.0, m=512, eps=0.1, delta=1e-3):
    return argparse.Namespace(steps=steps, tau=tau, local_samples=m,
                              epsilon=eps, delta=delta)


def test_manifest_extra_roundtrip(tmp_path):
    from repro.core.porter import porter_init
    from repro.launch.checkpoint import (read_manifest, restore_state,
                                         save_state)
    state = porter_init({"w": jnp.ones(5)}, n_agents=2)
    extra = {"rounds_executed": 12, "sigma_p": 0.25}
    save_state(tmp_path, state, step=12, extra=extra)
    man = read_manifest(tmp_path)
    assert man["extra"] == extra and man["step"] == 12
    restored = restore_state(tmp_path, like=state)  # extra is inert
    np.testing.assert_array_equal(np.asarray(restored.x["w"]),
                                  np.asarray(state.x["w"]))


def test_resolve_privacy_fresh_vs_resume():
    from repro.api import algorithm_info
    from repro.core import calibrate_sigma
    from repro.launch.train import resolve_privacy

    info = algorithm_info("porter-dp")
    args = _train_args()
    sigma, acct, prev = resolve_privacy(info, args, 0, {})
    assert prev == 0 and acct.steps == 0
    assert sigma == pytest.approx(calibrate_sigma(
        args.tau, args.steps, args.local_samples, args.epsilon, args.delta))

    # resume: sigma pinned to the manifest, accountant pre-advanced by the
    # rounds actually executed -- NOT re-calibrated for the full horizon
    extra = {"rounds_executed": 10, "sigma_p": 0.5}
    sigma_r, acct_r, prev_r = resolve_privacy(info, args, 10, extra)
    assert sigma_r == 0.5 and prev_r == 10 and acct_r.steps == 10
    eps_10 = acct_r.epsilon(args.delta)
    acct_r.step(30)  # the remaining rounds of the 40-step target
    assert acct_r.epsilon(args.delta) > eps_10  # eps grows with spend

    # non-dp algorithms skip accounting but keep the round count
    info_gc = algorithm_info("porter-gc")
    sigma_gc, acct_gc, prev_gc = resolve_privacy(info_gc, args, 7,
                                                 {"rounds_executed": 7})
    assert sigma_gc == 0.0 and acct_gc is None and prev_gc == 7

    # changing tau or local_samples across a resume mixes rounds run under
    # different clipping/noise regimes: refuse, don't mis-state eps
    extra_tau = {"rounds_executed": 10, "sigma_p": 0.5, "tau": 2.0,
                 "local_samples": args.local_samples}
    with pytest.raises(ValueError, match="tau"):
        resolve_privacy(info, args, 10, extra_tau)
    extra_m = {"rounds_executed": 10, "sigma_p": 0.5, "tau": args.tau,
               "local_samples": 9999}
    with pytest.raises(ValueError, match="local-samples"):
        resolve_privacy(info, args, 10, extra_m)

    # a DP resume from a checkpoint with no sigma_p metadata cannot be
    # accounted for -- refuse rather than re-calibrate over spent rounds
    with pytest.raises(ValueError, match="no sigma_p"):
        resolve_privacy(info, args, 10, {})


def test_dealias_copies_arrays_that_share_a_buffer():
    # two array objects over one buffer (device_put to the sharding it
    # already has) must not both be donated
    from repro.launch.runtime import _dealias
    a = jnp.arange(8.0)
    b = jax.device_put(a, a.sharding)
    assert b is not a
    out = _dealias({"a": a, "b": b, "c": a})
    ptrs = [out[k].addressable_shards[0].data.unsafe_buffer_pointer()
            for k in "abc"]
    assert len(set(ptrs)) == 3
    for k in "abc":
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(a))
