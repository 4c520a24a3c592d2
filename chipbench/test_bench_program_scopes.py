"""CPU tests of the readers of the program's own scopes and spans
(``scopes.py`` and the ``metrics/`` readers that use it), on made-up
device events whose scope paths nest as the compiled program's op_names
do, and on a host profile recorded here."""

import dataclasses

import pytest

from chipbench import scopes as S
from chipbench import trace as T
from chipbench.run import load_reader

ROOT = "jit(run_chunk)/while/body/closed_call/"
KERNEL = T.KERNEL
# (start, end, scope below ROOT, category), times in ns
EVENTS = [
    (0, 100, "oracle/vmap()/jvp(grad_oracle)/dot_general", "fusion"),
    # a second op under the oracle that overlaps the first: counted once
    (50, 80, "oracle/vmap()/jvp(grad_oracle)/add", "fusion"),
    (100, 130, "oracle/vmap()/oracle.clip/mul", "fusion"),
    (130, 150, "oracle/vmap()/oracle.noise/add", "fusion"),
    (150, 400, "engine.compress/compress/vmap()/top_k", "sort"),
    (400, 420, "engine.mix/ij,j...->i.../dot_general", "fusion"),
    (420, 450, "engine.ef_update/jit(ef_track)/pallas_call", KERNEL),
    (450, 500, "engine.ef_update/jit(sr_cast)/engine.sr_bits/xor", "fusion"),
    (500, 510, "step.metrics/reduce_sum", "fusion"),
    (510, 515, "runner.batch/jit(_randint)/add", "fusion"),
    # a codec executor packs inside its mix: compression, not gossip
    (515, 525, "engine.mix/shard_map/engine.compress/abs", "fusion"),
    # XLA's own copy (no op_name) and an op outside every program scope
    (530, 545, None, "copy"),
    (545, 550, "jit(_threefry_fold_in)/add", "add"),
    # idle from 550 to 600
    (600, 610, "step.metrics/reduce_sum", "fusion"),
]


def _events(rows):
    return [T.Event(f"{cat}.{i}", s, e, cat, "" if sc is None else ROOT + sc)
            for i, (s, e, sc, cat) in enumerate(rows)]


def _ctx(rows, rounds=1, chips=1):
    evs = _events(rows)
    return dict(devices=[evs] * chips, lo=0, hi=700, rounds=rounds)


def _read(name, ctx):
    return load_reader(name)(ctx)


def test_nesting_is_counted_once():
    ctx = _ctx(EVENTS)
    # [0, 150): the forward/backward pair overlaps, clip and noise inside
    assert _read("oracle.ms", ctx) == pytest.approx(150 / 1e6)
    assert _read("oracle.clip_ms", ctx) == pytest.approx(30 / 1e6)
    assert _read("oracle.noise_ms", ctx) == pytest.approx(20 / 1e6)


def test_sr_bits_lie_inside_the_ef_update():
    ctx = _ctx(EVENTS, rounds=2)
    assert _read("engine.ef_update_ms", ctx) == pytest.approx(80 / 2e6)
    assert _read("engine.sr_bits_ms", ctx) == pytest.approx(50 / 2e6)


def test_mix_leaves_out_the_codec_packing():
    assert _read("engine.mix_ms", _ctx(EVENTS)) == pytest.approx(20 / 1e6)


def test_unscoped_plus_scoped_is_busy():
    ctx = _ctx(EVENTS, chips=2)
    evs = ctx["devices"][0]
    busy = T.busy_ns(evs, 0, 700)
    scoped = T.matching_ns(evs, 0, 700, S.in_any_program_scope)
    assert busy == 555 and scoped == 535
    assert _read("step.unscoped_ms", ctx) == pytest.approx((busy - scoped)
                                                           / 1e6)


def test_an_absent_scope_gives_no_reading():
    no_noise = [r for r in EVENTS if "oracle.noise" not in (r[2] or "")]
    assert _read("oracle.noise_ms", _ctx(no_noise)) is None
    assert _read("oracle.ms", _ctx(no_noise)) is not None
    # a program that names no layer (an older commit): no reading at all,
    # the benchmark's own scopes notwithstanding
    bare = [(s, e, None if sc is None else sc.replace("engine.", "x_")
             .replace("oracle", "y").replace("step.", "z_")
             .replace("runner.", "w_"), c) for s, e, sc, c in EVENTS]
    for name in ("oracle.ms", "oracle.clip_ms", "oracle.noise_ms",
                 "engine.ef_update_ms", "engine.sr_bits_ms", "engine.mix_ms",
                 "step.unscoped_ms"):
        assert _read(name, _ctx(bare)) is None, name
    assert _read("oracle.ms", _ctx(EVENTS, rounds=0)) is None


def test_scopes_match_whole_path_elements():
    e = _events([(0, 10, "engine.ef_update_x/add", "fusion")])[0]
    assert not S.in_any_program_scope(e)
    assert S.in_any_program_scope(dataclasses.replace(
        e, scope=ROOT + "jvp(oracle)/add"))


def test_dispatch_spans_are_read_from_the_profile(tmp_path, monkeypatch):
    """``runner.dispatch_ms`` reads the runner's host spans from the
    traced run's profile; ``trace.reduce`` keeps only the benchmark's own
    host spans, so the traced window is what it was without them."""
    import time

    import jax

    jax.profiler.start_trace(str(tmp_path / "cell"))
    try:
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            for t in (0, 2, 4):
                with jax.profiler.TraceAnnotation("runner.dispatch",
                                                  start=t):
                    time.sleep(0.002)
        with jax.profiler.TraceAnnotation("runner.on_chunk", start=4):
            pass
    finally:
        jax.profiler.stop_trace()
    path = T.trace_file(tmp_path)
    spans = S.program_spans(path)
    assert [e.name for e in spans] == ["runner.dispatch"] * 3 + [
        "runner.on_chunk"]
    tr = T.reduce(path)
    assert [h.name for h in tr.host] == ["chipbench.dispatch"]
    lo, hi = tr.window()
    assert (lo, hi) == (tr.host[0].start, tr.host[0].end)

    dispatch = [e for e in spans if e.name == "runner.dispatch"]
    mean = sum(e.dur for e in dispatch) / 3 / 1e6
    assert mean >= 2.0
    monkeypatch.setattr(S, "TRACE_DIR", tmp_path)
    ctx = dict(devices=[], lo=lo, hi=hi, rounds=6)
    assert _read("runner.dispatch_ms", ctx) == pytest.approx(mean)
    # spans outside the window are not the window's
    assert _read("runner.dispatch_ms", {**ctx, "lo": hi + 1,
                                        "hi": hi + 2}) is None
    monkeypatch.setattr(S, "TRACE_DIR", tmp_path / "none")
    assert _read("runner.dispatch_ms", ctx) is None
