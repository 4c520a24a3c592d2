"""Build one benchmark cell from its data files.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``).
This module is the one general generator that reads both: it makes the
weights and the token stream from the seed, builds the registry algorithm
through ``repro.api.build`` (as ``repro.launch.train`` does), and the chunk
runner through ``repro.launch.runtime.make_runner``.

The weights and tokens are the benchmark's own (``make_params`` /
``make_source`` below), so the plain reference can make the same ones
from the seed without taking anything the program made.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# named scopes that the per-layer readers find in the device trace
GRAD_SCOPE = "grad_oracle"
COMPRESS_SCOPE = "compress"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_entry(workload: str, bench_path: Path = ROOT / "BENCHMARK.json"):
    """The cell's entry in BENCHMARK.json and the benchmark itself."""
    bench = load_json(bench_path)
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w, bench
    raise SystemExit(f"chipbench: no workload {workload!r} in {bench_path}")


def load_cell(workload: str, bench_path: Path = ROOT / "BENCHMARK.json"):
    """(entry, benchmark, config, traffic, limits) of a cell named in
    BENCHMARK.json, from the ``chipbench/`` directory beside it."""
    entry, bench = benchmark_entry(workload, bench_path)
    base = Path(bench_path).parent / "chipbench"
    config = load_json(base / "configs" / f"{entry['config']}.json")
    traffic = load_json(base / "traffic" / f"{entry['traffic']}.json")
    limits = load_json(base / "limits" / f"{workload}.json")
    if traffic["chips"] != entry["chips"]:
        raise ValueError(f"{workload}: BENCHMARK.json asks for "
                         f"{entry['chips']} chips, the traffic for "
                         f"{traffic['chips']}")
    return entry, bench, config, traffic, limits


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits of it."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def stream_keys(seed: int):
    """(weights key, base key of the runner's round stream)."""
    kw, kr = jax.random.split(seed_key(seed))
    return kw, kr


# ---------------------------------------------------------------------------
# model configuration: the JSON file -> the program's ModelConfig
# ---------------------------------------------------------------------------

def field_map(config: dict) -> dict:
    """The config file's ``program_fields``: for each field of the program's
    ModelConfig that the file sets, the file's (published) key."""
    return config["program_fields"]


def vocab_size(config: dict) -> int:
    """Rows of the vocabulary the cell runs (token ids are drawn below it)."""
    return config[field_map(config)["vocab"]]


def model_config(config: dict):
    """The program's config with the file's ``reduced`` keys set; every
    other key that ``program_fields`` names must already agree with the
    program's.  A field read through a property (the program's ``hd``) is
    set through the field that ``program_setters`` names for it."""
    from repro.configs import get_config

    cfg = get_config(config["arch"])
    fmap = field_map(config)
    setters = config.get("program_setters", {})
    cut = {setters.get(f, f): config[k] for f, k in fmap.items()
           if k in config.get("reduced", [])}
    cfg = dataclasses.replace(cfg, remat=False, **cut)
    for f, k in fmap.items():
        got = getattr(cfg, f)
        if got != config[k]:
            raise ValueError(f"{config['arch']}: the program runs {f}={got}, "
                             f"the config file states {k}={config[k]}")
    return cfg


# ---------------------------------------------------------------------------
# weights and tokens from the seed (the benchmark's own, shared with the
# reference)
# ---------------------------------------------------------------------------

def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def init_leaf(key, name: str, shape, dtype=jnp.float32):
    """Norm scales 1, biases and embeddings N(0, 0.02), weight matrices
    N(0, 1/fan_in)."""
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return jnp.ones(shape, dtype)
    if last in ("b", "table"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(
        jnp.asarray(shape[-2], dtype))


def param_names(shapes) -> list:
    """Leaf names of a parameter tree, in flatten order."""
    return [leaf_name(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(shapes)[0]]


def make_params(shapes, key):
    """One tree of f32 weights with ``shapes``' structure (traceable)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [init_leaf(jax.random.fold_in(key, i), leaf_name(p), s.shape)
              for i, (p, s) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_source(n_agents: int, batch: int, seq: int, vocab: int):
    """The token stream: ``(key, round) -> {"tokens": (n, b, s)}``, ids
    uniform over the vocabulary slice, every row its own draw."""
    def source(key, step):
        del step
        return {"tokens": jax.random.randint(key, (n_agents, batch, seq), 0,
                                             vocab, dtype=jnp.int32)}
    return source


def dp_sigma(traffic: dict) -> float:
    """Theorem-1 noise for the traffic's DP parameters (0 without DP):
    sigma = tau sqrt(T log(1/delta)) / (m eps)."""
    dp = traffic.get("dp")
    if not dp:
        return 0.0
    return (traffic["tau"] * math.sqrt(dp["horizon"] * math.log(1 / dp["delta"]))
            / (dp["local_samples"] * dp["epsilon"]))


def adjacency(traffic: dict):
    """The traffic's communication graph as a boolean (n, n) matrix: the
    ``edges`` it lists (pairs of agents, undirected), or else the graph its
    ``topology`` names, of those the benchmark builds itself (ring,
    complete, star).  Both the program (``build``) and the reference take
    the graph from here, so a new graph is a traffic file's edges."""
    import numpy as np
    n = traffic["agents"]
    adj = np.zeros((n, n), bool)
    if "edges" in traffic:
        pairs = traffic["edges"]
    elif traffic["topology"] == "ring":
        pairs = [(i, (i + 1) % n) for i in range(n)]
    elif traffic["topology"] == "complete":
        pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
    elif traffic["topology"] == "star":
        pairs = [(0, j) for j in range(1, n)]
    else:
        raise ValueError(f"topology {traffic['topology']!r}: list its "
                         "edges in the traffic file")
    for i, j in pairs:
        adj[i, j] = adj[j, i] = True
    np.fill_diagonal(adj, False)
    return adj


def edges_topology(traffic: dict):
    """The program's Topology (Metropolis weights) over the traffic's
    listed edges."""
    from repro.core.mixing import Topology, mixing_matrix, mixing_rate
    adj = adjacency(traffic).astype(float)
    w = mixing_matrix(adj, "metropolis")
    return Topology(kind="edges", n=traffic["agents"], adjacency=adj, w=w,
                    alpha=mixing_rate(w))


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    traffic: dict
    runner: Any              # repro.launch.runtime.ChunkRunner
    param_shapes: Any
    init: Any                # jitted: weights key -> algorithm state
    # where the round stream's key lives: the runner's own sharding of it,
    # so that the first chunk and the ones after it are one program
    key_sharding: Any = None

    @property
    def tokens_per_round(self) -> int:
        t = self.traffic
        return t["agents"] * t["batch"] * t["seq"]


def build(config: dict, traffic: dict, *, step_wrap=None, topology=None,
          comm_backend: str = "auto", interpret=None, devices=None) -> Cell:
    """The cell's algorithm, chunk runner and state initializer.

    ``step_wrap``/``topology`` exist for the CPU tests only: a fault planted
    under the timed path (a broken step, or a mixing matrix override).
    ``comm_backend``/``interpret``/``devices`` let ``aot_size.py`` compile
    the chip's kernels on a host without one, for described devices.
    """
    from repro import api
    from repro.core.comm_round import compress_stacked
    from repro.launch.runtime import make_runner
    from repro.models import build_model

    if traffic["chips"] > 1:
        if topology is not None:
            raise ValueError("build_train_step takes no topology override")
        return _build_agent_per_chip(config, traffic, comm_backend,
                                     step_wrap, devices)
    cfg = model_config(config)
    bundle = build_model(cfg)

    def loss(params, batch):
        with jax.named_scope(GRAD_SCOPE):
            return bundle.loss(params, batch)

    spec = api.ExperimentSpec(
        algo=traffic["algo"], n_agents=traffic["agents"],
        topology=traffic["topology"], compressor=traffic["compressor"],
        frac=traffic["frac"], gossip_mode=traffic["gossip_mode"],
        wire=traffic["wire"], plane_dtype=traffic["plane_dtype"],
        eta=traffic["eta"], tau=traffic["tau"], sigma_p=dp_sigma(traffic),
        comm_backend=comm_backend, interpret=interpret)
    compress_fn = None
    if traffic["wire"] == "dense":
        comp = api.resolve_compressor(spec)

        def compress_fn(key, tree):
            with jax.named_scope(COMPRESS_SCOPE):
                return compress_stacked(comp, key, tree)

    if topology is None and "edges" in traffic:
        topology = edges_topology(traffic)
    algo = api.build(spec, loss, compress_fn=compress_fn,
                     topology=topology)
    step = algo.step if step_wrap is None else step_wrap(algo.step)
    source = make_source(traffic["agents"], traffic["batch"], traffic["seq"],
                         cfg.vocab)
    runner = make_runner(step, source, traffic["chunk"])
    shapes = jax.eval_shape(lambda k: bundle.init(k)[0],
                            jax.random.PRNGKey(0))
    init = jax.jit(lambda k: algo.init(make_params(shapes, k)))
    return Cell(traffic=traffic, runner=runner,
                param_shapes=shapes, init=init)


def _build_agent_per_chip(config: dict, traffic: dict, comm_backend: str,
                          step_wrap=None, devices=None):
    """One agent per chip: the algorithm, state and batch shardings from
    ``repro.launch.steps.build_train_step`` on ``make_host_mesh`` (the
    program's own multi-chip launch path, remat off), with the benchmark's
    weights and tokens."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import api
    from repro.launch import shapes as SH
    from repro.launch.mesh import make_host_mesh
    from repro.launch.runtime import make_runner
    from repro.launch.steps import build_train_step

    n = traffic["agents"]
    if n != traffic["chips"]:
        raise ValueError("one agent per chip: agents must equal chips")
    cfg = model_config(config)
    mesh = make_host_mesh(n, devices=(devices or jax.devices())[:n])
    shape = SH.ShapeSpec("chipbench", traffic["seq"], n * traffic["batch"],
                         "train")
    variants = [v for v, a in api.VARIANT_TO_ALGO.items()
                if a == traffic["algo"]]
    if not variants:
        raise ValueError(f"build_train_step runs none of "
                         f"{sorted(api.VARIANT_TO_ALGO.values())}; the "
                         f"traffic states {traffic['algo']}")
    if "edges" in traffic:
        raise ValueError("build_train_step takes a topology kind, not edges")
    setup = build_train_step(
        cfg, mesh, shape, variant=variants[0],
        gossip_mode=traffic["gossip_mode"],
        compressor_name=traffic["compressor"], frac=traffic["frac"],
        topology_kind=traffic["topology"], tau=traffic["tau"],
        sigma_p=dp_sigma(traffic), plane_dtype=traffic["plane_dtype"],
        remat=False, comm_backend=comm_backend, wire=traffic["wire"])
    algo = setup.algorithm
    if traffic["algo"] != algo.name or traffic["eta"] != algo.config.eta:
        raise ValueError(f"build_train_step runs {algo.name} at eta "
                         f"{algo.config.eta}; the traffic states "
                         f"{traffic['algo']} at eta {traffic['eta']}")
    source = make_source(n, traffic["batch"], traffic["seq"], cfg.vocab)
    step = algo.step if step_wrap is None else step_wrap(algo.step)
    runner = make_runner(step, source, traffic["chunk"],
                         state_sharding=setup.state_shardings,
                         batch_sharding=setup.batch_shardings)
    shapes = jax.eval_shape(lambda k: setup.bundle.init(k)[0],
                            jax.random.PRNGKey(0))
    init = jax.jit(lambda k: algo.init(make_params(shapes, k), n_agents=n),
                   out_shardings=setup.state_shardings)
    return Cell(traffic=traffic, runner=runner, param_shapes=shapes,
                init=init, key_sharding=NamedSharding(mesh, P()))
