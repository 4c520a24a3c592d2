"""CPU tests of the trace reduction, on a small trace recorded on the chip.

``testdata/trace_small.textproto`` holds 430-odd op events of one chunk of
``minicpm3-gc`` on a TPU v5 lite (a while container, comm-round ops with six
Mosaic kernels, a top_k sort), the benchmark's host span over them, and a
second device with made-up collectives.  Each number is checked against a
plain recount: coverage by walking every boundary, kernel time by summing
durations of events that never overlap.
"""

from pathlib import Path

import pytest

from chipbench import trace as T

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    text = (HERE / "testdata" / "trace_small.textproto").read_text()
    # two instructions put under the benchmark's scopes, as the compiled
    # program's op_names would
    scopes = {"fusion.2132": "jit(run_chunk)/while/body/jvp(grad_oracle)/add",
              "sort.482": "jit(run_chunk)/while/body/compress/sort"}
    return T.reduce(ProfileData.from_text_proto(text), scopes)


def _recount(intervals):
    """Covered time by sweeping every boundary (no merging)."""
    points = sorted({p for iv in intervals for p in iv})
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in intervals))


def test_containers_are_left_out(tr):
    dev = tr.devices["/device:TPU:0"]
    assert len(dev) == 431
    assert not any(e.category in T.CONTAINERS for e in dev)
    assert [h.name for h in tr.host] == ["chipbench.wait"]


def test_busy_union_and_idle_share(tr):
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    busy = T.busy_ns(dev, lo, hi)
    assert busy == _recount([(e.start, e.end) for e in dev])
    assert busy == 1_143_095_254
    idle = 1 - busy / (hi - lo)
    assert 0 < idle < 1e-4


def test_kernel_time(tr):
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    kernels = [e for e in dev if T.is_kernel(e)]
    assert sorted(T.family(e) for e in kernels) == (
        ["ef_track"] * 3 + ["sr_cast"] * 3)
    assert T.matching_ns(dev, lo, hi, T.is_kernel) == sum(
        e.dur for e in kernels) == 6_118_991


def test_scope_time(tr):
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    grad = [e for e in dev if e.name == "fusion.2132"]
    sort = [e for e in dev if e.name == "sort.482"]
    assert len(grad) == 1 and len(sort) == 1
    assert T.matching_ns(dev, lo, hi, T.in_scope("grad_oracle")) == grad[0].dur
    assert T.matching_ns(dev, lo, hi, T.in_scope("compress")) == sort[0].dur
    assert T.family(sort[0]) == "sort@compress"
    # a scope name that is only part of a path element does not match
    assert T.matching_ns(dev, lo, hi, T.in_scope("grad")) == 0


def test_exposed_collective_time(tr):
    """Collectives [5, 15] and [30, 36] us beside compute [0, 10] and
    [20, 24] us: 5 + 6 us with nothing else running."""
    dev = tr.devices["/device:TPU:1"]
    assert T.exposed_ns(dev, 0, 10**9) == 11_000
    assert T.matching_ns(dev, 0, 10**9, T.is_gossip) == 16_000


def test_top_ops_and_idle_gaps(tr):
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    top = T.top_ops(dev, lo, hi)
    assert top[0][0] == "sort@compress" and len(top) <= 10
    assert len(T.idle_gaps(dev, tr.host, lo, hi)) == 10
    gaps = T.idle_gaps(dev, tr.host, lo, hi, n=10**6)
    assert all(doing == "chipbench.wait" for doing, _ in gaps)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(
        (hi - lo) - T.busy_ns(dev, lo, hi), abs=10 * len(gaps))


def test_op_names_of_compiled_text():
    text = ('  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%c, metadata={op_name="jit(f)/jvp(grad_oracle)/mul" '
            'source_file="x.py" source_line=3}\n'
            '  ROOT %sort.2 = f32[8]{0} sort(f32[8]{0} %q), dimensions={0}, '
            'metadata={op_name="jit(f)/compress/sort"}\n'
            '  %copy.1 = f32[8]{0} copy(f32[8]{0} %r)\n')
    assert T.op_names(text) == {"fusion.7": "jit(f)/jvp(grad_oracle)/mul",
                                "sort.2": "jit(f)/compress/sort"}
    name, cat, scope = T.parse_op(
        '%sort.2 = (bf16[2,8]{1,0}, s32[2,8]{1,0}) sort(bf16[2,8]{1,0} %a)',
        T.op_names(text))
    assert (name, cat, scope) == ("sort.2", "sort", "jit(f)/compress/sort")


def test_ef_update_roofline_counts_only_the_ef_kernels(tr):
    """The share divides the EF bytes by the time of ef_track, ef_step and
    sr_cast alone: a codec kernel on the line adds nothing to it, and a
    line with no EF kernel gives no reading."""
    import dataclasses
    from chipbench.run import load_reader
    read = load_reader("ef_update_roofline")
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window()
    ctx = dict(devices=[dev], lo=lo, hi=hi, rounds=1, chips=1,
               round_bytes=819_000, peaks={"hbm_bytes_per_s": 819e9})
    # 819 kB at 819 GB/s is 1 us against 6_118_991 ns of EF kernels
    assert read(ctx) == pytest.approx(100.0 * 1e3 / 6_118_991)
    codec = dataclasses.replace(dev[0], name="wire_topk_pack.7",
                                category=T.KERNEL, start=hi, end=hi + 10**9)
    assert read({**ctx, "devices": [dev + [codec]], "hi": hi + 10**9}) == (
        read(ctx))
    others = [e for e in dev if not T.is_kernel(e)] + [codec]
    assert read({**ctx, "devices": [others]}) is None
