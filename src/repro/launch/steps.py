"""Step builders: jit-able, sharded train / prefill / serve steps for any
(architecture x input shape x mesh) combination.

``build_train_step`` wires the full PORTER stack around a model bundle:
agent-stacked parameters + EF/tracking buffers sharded over the agent axes,
tensor parallelism over 'model', gossip over the agent axes.

``build_prefill_step`` / ``build_serve_step`` wire the inference paths
(PORTER is a training-time algorithm; serving uses a single replica).

Everything here is *abstract-friendly*: shapes come from eval_shape, no
parameter is ever materialized, so grok-1-314b lowers on one CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import api
from repro.core import PorterConfig
from repro.core.porter import PorterState
from repro.models import ModelBundle, ModelConfig, build_model
from repro.nn.module import prepend_axis_specs
from . import shapes as SH
from .mesh import agent_axes, n_agents

__all__ = ["abstract_init", "build_train_step", "build_prefill_step",
           "build_serve_step", "make_shard_local_compress", "TrainSetup",
           "ServeSetup"]


def make_shard_local_compress(comp, mesh: Mesh, leaf_specs):
    """Shard-local compression: run the compressor inside shard_map so top-k
    selection never crosses a shard boundary.

    The naive path (flatten leaf -> global blocks -> top-k) reshapes across
    the model-sharded dimension, which XLA SPMD can only implement by
    all-gathering the entire buffer over the model axis -- measured at
    ~930 GiB/step for rwkv6-7b train_4k (see EXPERIMENTS.md SPerf).  Applying
    the compressor per shard keeps selection local; per-shard top-k is block
    top-k with shard-sized blocks, still a valid rho-compressor
    (Definition 3), and composes with the packed wire format.

    Only deterministic compressors are supported (the paper's top-k family);
    randomized ones would need per-shard keys threaded through shard_map.
    """
    if not comp.deterministic:
        raise ValueError("shard-local compression needs a deterministic "
                         "compressor (top_k / block_top_k)")

    def compress(key, tree):
        del key  # deterministic

        def run(t):
            return jax.tree_util.tree_map(lambda l: comp(None, l), t)

        fn = jax.shard_map(run, mesh=mesh, in_specs=(leaf_specs,),
                           out_specs=leaf_specs, check_vma=False)
        return fn(tree)

    return compress


def abstract_init(bundle: ModelBundle, key=None):
    """(param ShapeDtypeStructs, PartitionSpecs) without materializing."""
    if key is None:
        key = jax.random.PRNGKey(0)
    box = {}

    def wrapper(k):
        values, specs = bundle.init(k)
        box["specs"] = specs  # static python objects, captured during trace
        return values

    shapes = jax.eval_shape(wrapper, key)
    return shapes, box["specs"]


def _shardings(mesh: Mesh, specs):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass
class TrainSetup:
    cfg: ModelConfig
    bundle: ModelBundle
    jitted: Any                  # jit(step)
    state_shapes: Any            # PorterState of ShapeDtypeStruct
    batch_shapes: Any
    state_shardings: Any
    batch_shardings: Any
    key_shape: Any
    n_agents: int
    porter_cfg: PorterConfig
    algorithm: Any = None        # the built repro.api Algorithm

    def lower(self):
        return self.jitted.lower(self.state_shapes, self.batch_shapes,
                                 self.key_shape)

    def init_state(self, key) -> PorterState:
        params, _ = self.bundle.init(key)
        return self.algorithm.init(params, n_agents=self.n_agents)


def _state_partition_specs(state_shapes, stacked_specs, ax_entry):
    """PartitionSpecs for any registered algorithm's state NamedTuple.

    Param-shaped buffer trees (x, v, the EF surrogates and mirrors) share
    the agent-stacked leaf specs; bare 1-D fields are the ``(n,)`` push-sum
    weight planes, sharded over the agent axes like any agent-stacked
    buffer; bare scalars (the step counter) replicate.  Deriving this from
    the state's own shape keeps one launch path for every state layout
    (PorterState, PorterAdamState, DpCsgpState, ...) instead of
    hand-writing a spec tuple per algorithm.
    """
    def field_spec(val):
        if hasattr(val, "shape"):
            if val.ndim == 0:
                return P()
            if val.ndim == 1:
                return P(ax_entry)
        return stacked_specs

    return type(state_shapes)(*[field_spec(v) for v in state_shapes])


def build_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    shape: SH.ShapeSpec,
    variant: str = "gc",
    gossip_mode: str = "dense",
    compressor_name: str = "block_top_k",
    frac: float = 0.05,
    topology_kind: str = "ring",
    topology_schedule: Optional[str] = None,
    tau: float = 1.0,
    sigma_p: float = 0.0,
    buffer_dtype=jnp.float32,
    plane_dtype=None,
    remat: bool = True,
    remat_policy: Optional[str] = None,
    local_compress: bool = False,
    comm_backend: str = "auto",
    wire: str = "dense",
    overlap: bool = False,
) -> TrainSetup:
    """PORTER train step, sharded for ``mesh``.

    Construction is delegated to the ``repro.api`` facade (one
    ExperimentSpec -> Algorithm build), which owns the paper's stable
    hyper-parameter choices: gamma = (1-alpha) * rho / 2, eta from O(1/L)
    heuristics (configurable by the caller for real runs; the dry-run only
    needs a lowerable program).

    topology_schedule: optional time-varying topology spec string (see
    ``repro.api.ExperimentSpec.topology_schedule``); the schedule table is
    indexed by the state's step counter inside the compiled program, so the
    chunked runner still lowers one executable per chunk size.

    comm_backend: backend of the comm-round engine -- 'auto' runs the fused
    ef_track/ef_step Pallas kernels on TPU and the jnp reference elsewhere;
    shard-local compression and the packed wire format compose with either
    (compression/mixing stay in the pytree domain, only the AXPY chain runs
    over the flat tile planes).  The stacked leaf specs built here flow
    through ``api.build`` into the engine, so the pallas path runs its
    kernels on *per-shard planes* inside shard_map (kernels/flatten.py) --
    no pack/unpack reshard, 'pallas' is safe on every layout.

    wire: 'dense' ships f32 planes; 'packed_bits' ships the bit-packed
    buffers from ``repro.core.wire_formats`` (bf16+uint16 top-k segments or
    uint32 QSGD words).  Under packed_bits the wire codec runs *inside*
    shard_map, so selection is already per model shard -- it subsumes
    ``local_compress`` and the shard-local compressor is skipped (the
    ``lc_packed_bits`` sweep rung sets both; the engine would raise on the
    explicit compress_fn + codec combination).

    overlap: issue both comm rounds' collectives before either fused update
    (``CommRound(overlap=True)``); bit-exact to the sequential order.

    plane_dtype: storage dtype of the EF state planes ('bf16' halves the
    six non-master state buffers AND the gossip wire; master params stay
    f32 -- see ``repro.api.ExperimentSpec.plane_dtype``).

    remat_policy: jax.checkpoint policy around the loss/grad ('full' or
    'dots'); composes with the flax-level ``remat`` flag -- the model's
    internal remat decides *block* boundaries, this knob checkpoints the
    whole loss so eight agent-stacked state buffers fit beside the
    activations on the pod mesh.
    """
    cfg = dataclasses.replace(cfg, remat=remat)
    bundle = build_model(cfg)
    ax = agent_axes(mesh)
    n = n_agents(mesh)
    spec = api.ExperimentSpec(
        algo=api.VARIANT_TO_ALGO[variant],
        n_agents=n, topology=topology_kind, topology_weights="metropolis",
        topology_schedule=topology_schedule,
        compressor=compressor_name, frac=frac, gossip_mode=gossip_mode,
        comm_backend=comm_backend, wire=wire, overlap=overlap,
        eta=1e-3, tau=tau, sigma_p=sigma_p,
        buffer_dtype=buffer_dtype, plane_dtype=plane_dtype,
        remat_policy=remat_policy)

    # ---- abstract state & shardings ---------------------------------------
    params_shapes, pspecs = abstract_init(bundle)
    ax_entry = ax if len(ax) > 1 else ax[0]
    stacked_specs = prepend_axis_specs(pspecs, ax_entry)

    compress_fn = None
    if local_compress and wire == "dense":
        # packed_bits fuses (shard-local) selection into the wire codec;
        # building the explicit shard-local compressor too would make
        # api.build raise on the redundant combination.
        compress_fn = make_shard_local_compress(
            api.resolve_compressor(spec), mesh, stacked_specs)
    algo = api.build(spec, bundle.loss, mesh=mesh, agent_axes=ax,
                     leaf_specs=stacked_specs, compress_fn=compress_fn)
    pcfg = algo.config
    step = algo.step
    state_shapes = jax.eval_shape(
        lambda p: algo.init(p, n_agents=n, w=None), params_shapes)
    state_specs = _state_partition_specs(state_shapes, stacked_specs,
                                         ax_entry)
    batch_shapes, batch_specs = SH.train_batch_specs(cfg, shape, n, ax)

    state_sh = _shardings(mesh, state_specs)
    batch_sh = _shardings(mesh, batch_specs)
    repl = NamedSharding(mesh, P())
    metrics_sh = {k: repl for k in
                  ("loss", "consensus_x", "consensus_v", "v_norm",
                   "wire_bytes")}
    jitted = jax.jit(step,
                     in_shardings=(state_sh, batch_sh, repl),
                     out_shardings=(state_sh, metrics_sh))
    key_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return TrainSetup(cfg=cfg, bundle=bundle, jitted=jitted,
                      state_shapes=state_shapes, batch_shapes=batch_shapes,
                      state_shardings=state_sh, batch_shardings=batch_sh,
                      key_shape=key_shape, n_agents=n, porter_cfg=pcfg,
                      algorithm=algo)


# ---------------------------------------------------------------------------
# Inference
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeSetup:
    cfg: ModelConfig
    bundle: ModelBundle
    jitted: Any
    arg_shapes: Tuple
    param_shardings: Any

    def lower(self):
        return self.jitted.lower(*self.arg_shapes)


def _serve_param_specs(pspecs, fsdp_axis: Optional[str]):
    """Serving params: model-sharded; optionally FSDP over the data axis
    (beyond-paper memory optimization for big checkpoints)."""
    if fsdp_axis is None:
        return pspecs

    def add_fsdp(s: P) -> P:
        entries = list(tuple(s))
        for i, e in enumerate(entries):
            if e is None:
                entries[i] = fsdp_axis
                return P(*entries)
        return s

    return jax.tree_util.tree_map(add_fsdp, pspecs,
                                  is_leaf=lambda x: isinstance(x, P))


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, shape: SH.ShapeSpec,
                       fsdp: bool = False, remat: bool = False,
                       q_chunk=None) -> ServeSetup:
    cfg = dataclasses.replace(cfg, remat=remat, q_chunk=q_chunk)
    bundle = build_model(cfg)
    ax = agent_axes(mesh)
    nb = n_agents(mesh)
    params_shapes, pspecs = abstract_init(bundle)
    pspecs = _serve_param_specs(pspecs, "data" if fsdp else None)
    batch_shapes, batch_specs = SH.serve_token_specs(cfg, shape, ax, nb)
    param_sh = _shardings(mesh, pspecs)
    batch_sh = _shardings(mesh, batch_specs)
    jitted = jax.jit(bundle.prefill, in_shardings=(param_sh, batch_sh))
    return ServeSetup(cfg=cfg, bundle=bundle, jitted=jitted,
                      arg_shapes=(params_shapes, batch_shapes),
                      param_shardings=param_sh)


def build_serve_step(cfg: ModelConfig, mesh: Mesh, shape: SH.ShapeSpec,
                     fsdp: bool = False,
                     cache_dtype=jnp.bfloat16) -> ServeSetup:
    """One-token decode step with a seq_len-deep cache (greedy sampling).

    cache_dtype: bf16 default.  int8 halves cache footprint/traffic of the
    (memory-bound) decode shapes; NOTE this configuration currently measures
    the *traffic/memory* effect only -- numerically-correct int8 caching
    additionally needs per-head quantization scales on write/read, which the
    cache layout does not carry yet (documented gap, EXPERIMENTS SPerf-4)."""
    cfg = dataclasses.replace(cfg, remat=False)
    bundle = build_model(cfg)
    ax = agent_axes(mesh)
    nb = n_agents(mesh)
    window = SH.decode_window(cfg, shape)
    model_size = mesh.shape["model"]

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = bundle.decode_step(params, cache, tokens, pos,
                                               window=window)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_cache

    params_shapes, pspecs = abstract_init(bundle)
    pspecs = _serve_param_specs(pspecs, "data" if fsdp else None)
    bsz = shape.global_batch
    enc_len = min(shape.seq_len, 4096) if cfg.family == "encdec" else None
    cache_shapes = jax.eval_shape(
        lambda: bundle.init_cache(bsz, shape.seq_len, dtype=cache_dtype,
                                  window=window, enc_len=enc_len))
    cache_specs = SH.cache_pspecs(cache_shapes, ax, nb,
                                  model_size=model_size)
    tok_shapes, tok_specs = SH.serve_token_specs(cfg, shape, ax, nb)

    param_sh = _shardings(mesh, pspecs)
    cache_sh = _shardings(mesh, cache_specs)
    tok_sh = _shardings(mesh, tok_specs)
    repl = NamedSharding(mesh, P())
    jitted = jax.jit(serve_step,
                     in_shardings=(param_sh, cache_sh, tok_sh, repl),
                     out_shardings=(tok_sh, cache_sh))
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    return ServeSetup(cfg=cfg, bundle=bundle, jitted=jitted,
                      arg_shapes=(params_shapes, cache_shapes, tok_shapes,
                                  pos_shape),
                      param_shardings=param_sh)
