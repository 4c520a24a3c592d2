"""The program names the layers of a training round, and the names survive
compilation.

Nothing runs on a device here: each test compiles the chunk program of a
micro cell of ``chipbench/micro.py`` on the CPU and reads the op_names of
its HLO instructions (``chipbench.trace.op_names``).  ``gc`` and ``dp`` go
through ``repro.api.build`` with the jnp reference engine, and ``gc`` also
with the Pallas kernels in interpret mode; ``ring4`` (one agent per device,
ring gossip of bit-packed buffers, through ``build_train_step``) needs four
host devices and so a process of its own.  The last test records a host
profile of the chunk runner and reads its spans back.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SCOPES = ("oracle", "oracle.clip", "oracle.noise", "engine.compress",
          "engine.mix", "engine.ef_update", "engine.sr_bits", "step.metrics",
          "runner.batch")
_INSTR = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+ = .*)$")


def _parts(op_name: str) -> set:
    return set(op_name.replace("(", "/").replace(")", "/").split("/"))


def chunk_program_ops(kind: str, **build_kw):
    """[(instruction, opcode, op_name)] of a micro cell's compiled chunk
    program.  The bodies of reducers and comparators, whose op_names hold
    no path from the program's root, are left out."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from chipbench import cell as C
    from chipbench import micro
    from chipbench import trace as T

    bench = micro.write_bench(Path(tempfile.mkdtemp()))
    _, _, config, traffic, _ = C.load_cell(f"micro-{kind}", bench)
    cell = C.build(config, traffic, **build_kw)
    state = jax.eval_shape(cell.init, jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=cell.key_sharding)
    start = jax.ShapeDtypeStruct((), jnp.int32)
    text = cell.runner.jitted.lower(state, key, start).compile().as_text()
    names = T.op_names(text)
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, opcode, scope = T.parse_op(m.group(1), names)
            if not scope or scope.startswith("jit(run_chunk)/"):
                out.append((name, opcode, scope))
    return out


def _scopes_seen(ops) -> set:
    return {s for _, _, op_name in ops for s in SCOPES
            if s in _parts(op_name)}


VARIANTS = {"gc-ref": ("gc", {}),
            "gc-pallas": ("gc", {"comm_backend": "pallas",
                                 "interpret": True}),
            "dp-ref": ("dp", {})}


@pytest.fixture(scope="module", params=sorted(VARIANTS))
def program(request):
    kind, kw = VARIANTS[request.param]
    return kind, chunk_program_ops(kind, **kw)


def test_every_layer_is_named(program):
    kind, ops = program
    want = set(SCOPES) - ({"oracle.noise"} if kind == "gc" else set())
    assert _scopes_seen(ops) == want


def test_sorts_lie_under_compression(program):
    """The chunk program sorts nothing: the global top_k counts instead, and
    its counting reductions lie under ``engine.compress``."""
    _, ops = program
    assert not [o for _, opcode, o in ops if opcode == "sort"]
    counts = [p for p in (_parts(o) for _, opcode, o in ops
                          if opcode == "reduce") if "compress" in p]
    assert counts and all("engine.compress" in p for p in counts)


def test_benchmark_scopes_nest_in_the_programs(program):
    """The benchmark's ``grad_oracle`` (around the loss) and ``compress``
    (around its compress_fn) lie inside the program's own scopes."""
    _, ops = program
    for bench_scope, scope in (("grad_oracle", "oracle"),
                               ("compress", "engine.compress")):
        inner = [p for p in (_parts(o) for _, _, o in ops)
                 if bench_scope in p]
        assert inner and all(scope in p for p in inner), bench_scope


def test_sr_bits_nest_in_the_ef_update(program):
    _, ops = program
    bits = [p for p in (_parts(o) for _, _, o in ops)
            if "engine.sr_bits" in p]
    assert bits and all("engine.ef_update" in p for p in bits)


def _ring4_ops():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, __file__, "ring4"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def ring4():
    return _ring4_ops()


def test_ring_gossip_is_named(ring4):
    """On the ring of packed buffers every collective-permute lies under
    ``engine.mix``, and the pack inside the executor is ``engine.compress``
    although the launch path hands the engine no compress_fn (so no
    benchmark ``compress`` scope)."""
    permutes = [o for _, opcode, o in ring4 if opcode == "collective-permute"]
    assert permutes
    assert all("engine.mix" in _parts(o) for o in permutes)
    assert not any("compress" in _parts(o) for _, _, o in ring4)
    assert _scopes_seen(ring4) == set(SCOPES) - {"oracle.noise"}


def test_chunk_runner_host_spans(tmp_path):
    """``ChunkRunner.__call__`` and ``run_chunked``'s callback write
    ``runner.dispatch`` and ``runner.on_chunk`` spans on the profiler's
    clock, one per chunk, each carrying the chunk's start round."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from chipbench import scopes as S
    from chipbench import trace as T
    from repro.launch.runtime import run_chunked

    def step(state, batch, key):
        del key
        return state + jnp.sum(batch), {"loss": state}

    def source(key, t):
        del t
        return jax.random.normal(key, (4,))

    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_chunked(step, source, jnp.zeros(()), jax.random.PRNGKey(0), 5,
                    chunk=2, on_chunk=lambda t0, *_: seen.append(t0))
    finally:
        jax.profiler.stop_trace()
    path = T.trace_file(tmp_path)
    spans = S.program_spans(path)
    assert [e.name for e in spans] == ["runner.dispatch",
                                      "runner.on_chunk"] * 3
    assert seen == [0, 2, 4]
    for dispatch, callback in zip(spans[::2], spans[1::2]):
        assert dispatch.end <= callback.start
    starts = [dict(e.stats).get("start")
              for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for e in line.events
              if e.name == "runner.dispatch"]
    assert starts == [0, 2, 4]


if __name__ == "__main__":
    print(json.dumps(chunk_program_ops(sys.argv[1])))
