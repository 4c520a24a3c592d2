"""The comm-round engine: one fused EF/gossip primitive for every
compressed-communication algorithm in the repo.

Every compressed decentralized method here (PORTER, PORTER-Adam, BEER,
CHOCO-SGD, SoteriaFL) repeats the same per-round pattern around a buffer
``y`` with surrogate ``q`` and mixing mirror ``m``:

    c   =  C(y - q)          compress the increment        (hits the wire)
    q  +=  c                 surrogate accumulate          (local)
    m  +=  W c               mixing-mirror accumulate      (receive side)
    y'  =  f(y, m - q, ...)  algorithm-specific fused update

:class:`CommRound` owns that pattern once.  Compression and mixing run in
the *pytree domain* (so shard-local compressors and the ring/packed wire
executors keep their PartitionSpecs), while the AXPY chain of the update
runs leaf by leaf through :mod:`repro.kernels.flatten` so the fused
Pallas kernels (:mod:`repro.kernels.ef_update`) touch each parameter once
per round instead of ~13 separate HBM-bound tree_map passes.

Backends:

* ``'pallas'`` -- run ef_track / ef_step / ef_gossip on every state leaf
  (Mosaic on TPU; pass ``interpret=True`` for CPU CI).
* ``'ref'``    -- pure-jnp tree_map chain, bit-identical to the pre-engine
  per-algorithm bodies; the numerical oracle.
* ``'auto'``   -- 'pallas' on TPU, 'ref' elsewhere (the default, resolved
  by :func:`resolve_backend`: BENCH_comm.json measures pallas-interpret
  ~3x slower than ref on CPU, so off-TPU auto must mean ref).

Mixed precision (``plane_dtype='bf16'`` through the facade): the EF state
buffers (q, m, v, g_prev) live in bf16, so packed planes and the gossip
wire both carry 2 B/element while the master params ``x`` stay f32 exact
(each leaf's plane keeps that leaf's dtype -- see
:func:`repro.kernels.flatten.plane_apply`).  Every fused kernel
still accumulates in f32 inside the block; the writeback to a bf16 buffer
goes through the stochastic-rounding cast (:mod:`repro.kernels.sr_cast`)
so the EF drift stays unbiased, with the SR key split off the round key
(:meth:`CommRound.sr_split`) -- f32 engines never split, so their RNG
streams are bit-identical to the pre-mixed-precision code.  The push-sum
weight plane stays f32-exact on every path.

Sharding: when the engine is built with ``mesh`` + ``leaf_specs`` (every
layout the launch layer builds), the pallas path runs its kernels inside
``shard_map`` with those leaf specs, once per (agent shard x model shard)
and leaf (:func:`repro.kernels.flatten.plane_apply`):
a Mosaic kernel is one call the SPMD partitioner cannot split, so outside
``shard_map`` it would run on gathered buffers.  ``backend='pallas'`` is
therefore safe on every layout the launch layer builds.

Time-varying topologies: the engine's methods take the absolute round index
``t`` and forward it to the mixer (:func:`repro.core.gossip.apply_mixer`),
which gathers ``W_{t mod period}`` from its device-resident schedule table
inside the compiled program.  ``W_t`` therefore enters the round as a traced
value, and everything downstream of the mix -- including the fused ef_track
/ ef_step / ef_gossip plane kernels -- consumes ``wc = W_t @ c`` as data,
so the pallas path and the per-shard plane layout need no schedule plumbing
at all.

Push-sum (directed graphs): :meth:`CommRound.exchange_ps` /
:meth:`CommRound.step_ps` run the same round over a *column*-stochastic
``W_t`` while carrying the scalar push-sum weight plane (DP-CSGP's
de-biasing state, read points divide by it) through the **same**
collectives the param round already issues -- an extra flat column for
the dense/ring executors, +4 bitcast bytes on the codec buffers -- so
directed gossip adds zero communication ops (HLO-asserted) and the weight
increment is transported exactly (never compressed: compressing it would
break the column-mass invariant ``1^T W = 1^T`` that push-sum relies on).

Wire accounting: :meth:`CommRound.wire_bytes` converts (gossip mode,
compressor, n_agents, d) into per-round bytes via
:func:`repro.core.gossip.gossip_wire_bytes` / ``Compressor.wire_bits`` so
every algorithm reports the same ``wire_bytes`` metric and cross-algorithm
comparisons are apples-to-apples (benchmarks/ablation.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..kernels import flatten as FL
from ..kernels import ops
from . import wire_formats as WF
from .compression import Compressor
from .gossip import PACK_BLOCK, MixFn, apply_mixer, gossip_wire_bytes

__all__ = ["CommRound", "compress_stacked", "resolve_backend",
           "resolve_engine"]

CompressFn = Callable[[jax.Array, Any], Any]  # (key, tree) -> tree


def resolve_backend(backend: str) -> str:
    """Resolve 'auto' to a concrete comm-round backend for this process.

    'auto' means the fused pallas kernels *on TPU only*: off-TPU the
    kernels run in interpret mode, which BENCH_comm.json measures at ~3x
    the ref backend's wall time on every compressor (e.g. top_k 17483 vs
    5672 us/round on CPU), so auto resolves to 'ref' everywhere except a
    real TPU backend.  This is the single resolution point -- the engine
    and the facade's wire-format builder both call it, so they can never
    disagree.
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend not in ("pallas", "ref"):
        raise ValueError(f"unknown comm-round backend {backend!r}")
    return backend


def _sr_dtype(tree) -> bool:
    """True when ``tree``'s buffers take the stochastic-rounding writeback
    (bf16 -- the only sub-f32 plane dtype the engine supports)."""
    leaves = jax.tree_util.tree_leaves(tree)
    dt = jnp.result_type(*[l.dtype for l in leaves])
    return jnp.dtype(dt) == jnp.dtype(jnp.bfloat16)


def compress_stacked(comp: Compressor, key: jax.Array, tree):
    """Compress each agent's row of every leaf independently (paper setup:
    every agent compresses its own increment; per-leaf to match the
    convergence tests' rho accounting)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))

    def one(k, leaf):
        n = leaf.shape[0]
        ks = jax.random.split(k, n)
        return jax.vmap(lambda kk, row: comp(kk, row))(ks, leaf)

    return treedef.unflatten([one(k, l) for k, l in zip(keys, leaves)])


def _tree(op, *trees):
    return jax.tree_util.tree_map(op, *trees)


def resolve_engine(engine: Optional["CommRound"], mixer=None,
                   compressor: Optional[Compressor] = None,
                   compress_fn: Optional[CompressFn] = None,
                   backend: str = "auto",
                   interpret: Optional[bool] = None) -> "CommRound":
    """Return ``engine`` or build one from the pieces -- never both.

    When an ``engine`` is given it owns its compressor/mixer/compress_fn;
    passing a *different* object alongside it used to be silently ignored
    (the footgun: the positional pieces looked load-bearing but were not).
    Now it raises -- build the engine with the right pieces instead (the
    facade :func:`repro.api.build` / :func:`repro.api.build_engine` is the
    one place engines are constructed).

    ``mixer=None`` without an engine is allowed: server/client algorithms
    (SoteriaFL, DP-SGD accounting) compress without gossip.
    """
    if engine is not None:
        for what, given, owned in (("mixer", mixer, engine.mixer),
                                   ("compressor", compressor,
                                    engine.compressor),
                                   ("compress_fn", compress_fn,
                                    engine.compress_fn)):
            if given is not None and given is not owned:
                raise ValueError(
                    f"both engine= and a conflicting {what} were given; the "
                    f"engine owns its {what} -- pass the pieces the engine "
                    "was built with (or None), or rebuild it via "
                    "repro.api.build_engine")
        return engine
    if compressor is None:
        raise ValueError("need either engine= or a compressor")
    return CommRound(compressor=compressor, mixer=mixer,
                     compress_fn=compress_fn, backend=backend,
                     interpret=interpret)


@dataclasses.dataclass(frozen=True)
class CommRound:
    """One compressed communication round: compress -> accumulate -> update.

    Attributes:
      compressor: the rho-compressor (Definition 3); also drives wire
        accounting.
      mixer: gossip executor ``tree -> W @ tree`` over the agent axis
        (core.gossip); its ``wire_mode`` tag selects the wire format for
        byte accounting.
      compress_fn: optional (key, tree) -> tree override, e.g. the
        shard-local compressor from launch.steps.  Defaults to per-agent
        per-leaf compression of ``compressor``.
      backend: 'pallas' | 'ref' | 'auto'.
      interpret: Pallas interpret mode; None = auto (True off-TPU).
      mesh / leaf_specs / agent_axes: sharded-layout hooks (the facade
        ``repro.api.build_engine`` plumbs them from the launch layer).  With
        both set, the pallas path runs its kernels on per-shard planes
        inside ``shard_map``.
      overlap: comm/compute overlap.  The PORTER family runs *two* comm
        rounds per step whose exchanges are data-independent (the x-side
        inputs ``(x, q_x)`` are untouched by the v-side update); with
        ``overlap=True`` the algorithm steps issue both compress+collective
        pairs *before* either fused update, so XLA's async collectives run
        while the other round's local compute proceeds.  Every intermediate
        value is identical to the sequential order, so the flag is bit-exact
        by construction (tests pin this for all registered algorithms);
        single-round algorithms ignore it.
      plane_dtype: declared storage dtype of the EF state planes (None =
        legacy f32).  The *actual* plane dtype is always derived from the
        buffers themselves (so f32 master params keep f32 planes next to
        bf16 EF buffers); this field drives the scalar-``d`` wire-byte
        accounting and documents the engine's precision contract.  Must be
        f32 or bf16: the SR writeback targets bf16 only.

    Wire formats: when the mixer was built with a
    :class:`repro.core.wire_formats.WireFormat` codec (``spec.wire =
    "packed_bits"`` through the facade), :meth:`exchange` routes through
    ``mixer.exchange`` -- compression is *fused with packing* and only
    bit-packed buffers cross the wire; the locally applied increment is the
    round-trip ``c = unpack(pack(y - q))``, which keeps the ``m = W q``
    invariant exact.  :meth:`wire_bytes` then reports the **measured** nbytes
    of the shipped buffers (shapes traced with ``jax.eval_shape`` on the
    codec itself) and :meth:`wire_bytes_model` keeps the analytic byte model
    as a cross-check (``bench_comm_round.py --achieved-bytes`` asserts they
    agree).
    """

    compressor: Compressor
    mixer: MixFn
    compress_fn: Optional[CompressFn] = None
    backend: str = "auto"
    interpret: Optional[bool] = None
    mesh: Any = None
    leaf_specs: Any = None
    agent_axes: Sequence[str] = ("data",)
    overlap: bool = False
    plane_dtype: Any = None

    def __post_init__(self):
        if self.backend not in ("pallas", "ref", "auto"):
            raise ValueError(f"unknown comm-round backend {self.backend!r}")
        if self.plane_dtype is not None:
            pdt = jnp.dtype(self.plane_dtype)
            if pdt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
                raise ValueError(
                    f"plane_dtype must be f32 or bf16, got {pdt} -- the "
                    "stochastic-rounding writeback targets bf16 only")

    # -- backend plumbing ---------------------------------------------------

    def _use_pallas(self) -> bool:
        return resolve_backend(self.backend) == "pallas"

    def _kernel_kw(self):
        return {} if self.interpret is None else {"interpret": self.interpret}

    def _plane_mesh(self):
        """The mesh the kernels run per shard on, else None (one device)."""
        if self.mesh is None or self.leaf_specs is None:
            return None
        return self.mesh

    # -- stochastic-rounding plumbing ---------------------------------------

    def sr_split(self, key, trees) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Split an SR key off ``key`` when any of ``trees`` is bf16.

        Returns ``(compress_key, sr_key)``; for all-f32 buffers the key is
        returned untouched with ``sr_key=None``, so f32 engines keep their
        historical RNG streams bit-identical.  Overlap-mode algorithm steps
        call this before :meth:`exchange` with the same buffer tuple the
        sequential path passes internally, which keeps overlap==sequential
        bit-exact under mixed precision too.
        """
        if not any(_sr_dtype(t) for t in trees):
            return key, None
        # the SR writeback's key: a part of the EF update it feeds
        with jax.named_scope("engine.ef_update"), \
                jax.named_scope("engine.sr_bits"):
            k_c, k_sr = jax.random.split(key)
        return k_c, k_sr

    def _plane_update(self, kfn, trees, sr_key):
        """Fused 3-output kernel, leaf by leaf, with SR writeback when asked.

        ``kfn(*leaves, out_dtype=...)`` must return three arrays whose
        destinations are ``trees[:3]`` in order.  With an ``sr_key`` and
        any bf16 destination, the kernel is asked for f32 outputs and each
        bf16-bound output is stochastically rounded; f32 destinations pass
        through exact.  The SR key is folded with the
        leaf index, and under per-shard planes with every mesh axis index,
        so no two planes reuse bits.
        """
        mesh = self._plane_mesh()
        needs = [_sr_dtype(t) for t in trees[:3]]
        if sr_key is None or not any(needs):
            return FL.plane_apply(lambda _, *ls: kfn(*ls), trees, 3, mesh,
                                  self.leaf_specs)
        kw = self._kernel_kw()
        axis_names = tuple(mesh.axis_names) if mesh is not None else ()

        def kernel(leaf, *leaves):
            outs = kfn(*leaves, out_dtype=jnp.float32)
            with jax.named_scope("engine.sr_bits"):
                key = jax.random.fold_in(sr_key, leaf)
                for ax in axis_names:
                    key = jax.random.fold_in(key, jax.lax.axis_index(ax))
                keys = tuple(jax.random.split(key, 3))
            return tuple(ops.sr_cast(o, keys[i], **kw) if needs[i] else o
                         for i, o in enumerate(outs))

        return FL.plane_apply(kernel, trees, 3, mesh, self.leaf_specs)

    @staticmethod
    def _sr_writeback(tree_f32, like, key):
        """Cast an f32 result tree back to ``like``'s buffer dtypes (ref
        backend): stochastic rounding into bf16 leaves, plain astype into
        everything else."""
        leaves, treedef = jax.tree_util.tree_flatten(like)
        vals = jax.tree_util.tree_leaves(tree_f32)
        keys = jax.random.split(key, len(vals))
        out = []
        for val, l, kk in zip(vals, leaves, keys):
            if jnp.dtype(l.dtype) == jnp.dtype(jnp.bfloat16):
                out.append(ops.sr_cast_leaf(val, kk))
            else:
                out.append(val.astype(l.dtype))
        return treedef.unflatten(out)

    @staticmethod
    def _f32(tree):
        return _tree(lambda l: l.astype(jnp.float32), tree)

    # -- the shared front half: compress + mix ------------------------------

    def compress(self, key: jax.Array, delta):
        """c = C(delta), in the pytree domain (shard-local aware)."""
        with jax.named_scope("engine.compress"):
            if self.compress_fn is not None:
                return self.compress_fn(key, delta)
            return compress_stacked(self.compressor, key, delta)

    @staticmethod
    def _increment(y, q):
        """``y - q`` in the surrogate's dtype (see :meth:`exchange`)."""
        with jax.named_scope("engine.compress"):
            return _tree(lambda a, b: (a - b).astype(b.dtype), y, q)

    def exchange(self, key: jax.Array, y, q, t=None) -> Tuple[Any, Any]:
        """Compress the increment of ``y`` against surrogate ``q`` and mix.

        Returns ``(c, wc)`` with ``c = C(y - q)`` (what the agent puts on
        the wire) and ``wc = W @ c`` (what it accumulates off the wire).
        ``t`` is the absolute round index -- required (and traced) when the
        mixer runs a time-varying topology schedule, ignored otherwise; the
        fused plane kernels downstream consume ``wc`` as data, so the whole
        pallas path is schedule-agnostic.

        With a codec mixer (bit-packed wire format) the compression step is
        fused into the executor: pack once, apply the round-tripped
        increment locally, ship only the packed buffers.

        The increment is computed in the *surrogate's* dtype: with a bf16
        ``q`` beside the f32 master ``y = x``, a plain subtract would
        promote to f32 and put a 4 B/element buffer on the wire.  The
        narrowing is a deterministic cast (its error is measured afresh by
        the next round's ``y - q``, so EF self-corrects); stochastic
        rounding is reserved for the *accumulating* q/m/v writebacks where
        bias compounds.
        """
        delta = self._increment(y, q)
        if getattr(self.mixer, "wire_codec", None) is not None:
            # the codec executor packs (``engine.compress``) inside its mix
            with jax.named_scope("engine.mix"):
                return self.mixer.exchange(key, delta, t)
        c = self.compress(key, delta)
        return c, apply_mixer(self.mixer, c, t)

    def exchange_ps(self, key, y, q, yw, qw, t=None):
        """Push-sum exchange: :meth:`exchange` plus the scalar weight plane.

        ``yw``/``qw`` are the (n,) push-sum weight buffer and its surrogate.
        Returns ``(c, wc, cw, wcw)`` where ``(c, wc)`` are the compressed
        param increment and its mix exactly as in :meth:`exchange`, and
        ``cw = yw - qw`` (the weight increment, **never compressed** -- the
        column-mass invariant ``1^T W = 1^T`` breaks otherwise) with
        ``wcw = W_t @ cw``.  The weight rides *inside* the collectives the
        param round already issues (an extra flat column for dense/ring, +4
        bitcast bytes on the codec buffers), so the collective count is
        identical to :meth:`exchange` -- the HLO tests pin this.
        """
        delta = self._increment(y, q)
        dw = jnp.subtract(yw, qw)
        if getattr(self.mixer, "wire_codec", None) is not None:
            with jax.named_scope("engine.mix"):
                return self.mixer.exchange_ps(key, delta, dw, t)
        push = getattr(self.mixer, "push", None)
        if push is None:
            raise ValueError(
                "push-sum needs a mixer with weight-plane transport (the "
                "dense or ring executor, or a codec executor built with "
                "wire='packed_bits'); the plain packed all-gather mixer "
                "ships (value, index) pairs only and has no slot for the "
                "weight scalar -- use gossip='ring'/'dense' or a bit-packed "
                "wire format for directed (column-stochastic) topologies")
        c = self.compress(key, delta)
        with jax.named_scope("engine.mix"):
            wc, wcw = push(c, dw, t)
        return c, wc, dw, wcw

    # -- fused state updates ------------------------------------------------

    def track(self, key, v, q, m, g, g_prev, gamma: float, t=None):
        """PORTER Algorithm 1 lines 11-12 (gradient-estimate track).

        q += c; m += Wc; v' = v + gamma*(m - q) + g - g_prev.
        Returns (v', q', m').  ``t``: absolute round index for time-varying
        mixers (see :meth:`exchange`).
        """
        key, sr_key = self.sr_split(key, (q, m, v))
        c, wc = self.exchange(key, v, q, t)
        return self.track_update(c, wc, v, q, m, g, g_prev, gamma,
                                 sr_key=sr_key)

    def track_update(self, c, wc, v, q, m, g, g_prev, gamma: float,
                     sr_key=None):
        """The fused second half of :meth:`track` (no communication).

        Exposed separately so overlap mode can issue several exchanges
        before running any update (see the ``overlap`` attribute).
        ``sr_key``: stochastic-rounding key for bf16 buffers (from
        :meth:`sr_split`); None falls back to deterministic casts.
        """
        with jax.named_scope("engine.ef_update"):
            kw = self._kernel_kw()
            if self._use_pallas():
                qo, mo, vo = self._plane_update(
                    lambda *p, out_dtype=None: ops.ef_track(
                        *p, gamma, out_dtype=out_dtype, **kw),
                    (q, m, v, c, wc, g, g_prev), sr_key)
                return vo, qo, mo
            if sr_key is not None and any(_sr_dtype(t) for t in (q, m, v)):
                q2f = _tree(jnp.add, self._f32(q), self._f32(c))
                m2f = _tree(jnp.add, self._f32(m), self._f32(wc))
                v2f = _tree(lambda v0, mm, qq, gn, gp: v0 + gamma * (mm - qq)
                            + gn - gp, self._f32(v), m2f, q2f, self._f32(g),
                            self._f32(g_prev))
                kq, km, kv = jax.random.split(sr_key, 3)
                return (self._sr_writeback(v2f, v, kv),
                        self._sr_writeback(q2f, q, kq),
                        self._sr_writeback(m2f, m, km))
            q2 = _tree(jnp.add, q, c)
            m2 = _tree(jnp.add, m, wc)
            v2 = _tree(lambda v0, mm, qq, gn, gp: v0 + gamma * (mm - qq)
                       + gn - gp, v, m2, q2, g, g_prev)
            return v2, q2, m2

    def step(self, key, x, q, m, v, gamma: float, eta: float, t=None):
        """PORTER Algorithm 1 lines 13-14 (parameter step).

        q += c; m += Wc; x' = x + gamma*(m - q) - eta*v, cast to x.dtype.
        Returns (x', q', m').  ``v`` may be any descent direction (PORTER
        passes the tracked gradient, PORTER-Adam its preconditioned form).
        ``t``: absolute round index for time-varying mixers.
        """
        key, sr_key = self.sr_split(key, (q, m, x))
        c, wc = self.exchange(key, x, q, t)
        return self.step_update(c, wc, x, q, m, v, gamma, eta, sr_key=sr_key)

    def step_update(self, c, wc, x, q, m, v, gamma: float, eta: float,
                    sr_key=None):
        """The fused second half of :meth:`step` (no communication).

        ``sr_key``: stochastic-rounding key for bf16 buffers (the master
        params ``x`` normally stay f32 and take an exact writeback; only
        the q/m surrogates round stochastically).
        """
        with jax.named_scope("engine.ef_update"):
            kw = self._kernel_kw()
            if self._use_pallas():
                qo, mo, xo = self._plane_update(
                    lambda *p, out_dtype=None: ops.ef_step(
                        *p, gamma, eta, out_dtype=out_dtype, **kw),
                    (q, m, x, c, wc, v), sr_key)
                return xo, qo, mo
            if sr_key is not None and any(_sr_dtype(t) for t in (q, m, x)):
                q2f = _tree(jnp.add, self._f32(q), self._f32(c))
                m2f = _tree(jnp.add, self._f32(m), self._f32(wc))
                x2f = _tree(lambda x0, mm, qq, vv:
                            x0 + gamma * (mm - qq) - eta * vv,
                            self._f32(x), m2f, q2f, self._f32(v))
                kq, km, kx = jax.random.split(sr_key, 3)
                return (self._sr_writeback(x2f, x, kx),
                        self._sr_writeback(q2f, q, kq),
                        self._sr_writeback(m2f, m, km))
            q2 = _tree(jnp.add, q, c)
            m2 = _tree(jnp.add, m, wc)
            x2 = _tree(lambda x0, mm, qq, vv:
                       (x0 + gamma * (mm - qq) - eta * vv).astype(x0.dtype),
                       x, m2, q2, v)
            return x2, q2, m2

    def step_ps(self, key, x, q, m, v, xw, qw, mw, gamma: float, eta: float,
                t=None):
        """Push-sum parameter step: :meth:`step` plus the weight recursion.

        The param buffers update exactly as :meth:`step`; the (n,) weight
        planes follow the same EF/gossip recursion with the *exact*
        increment (``qw += cw; mw += W cw; xw' = xw + gamma*(mw - qw)``),
        which composes to ``xw' = ((1-gamma) I + gamma W) xw`` -- still
        column-stochastic, so the weights stay strictly positive and
        converge to ``n * pi`` (the Perron vector).  Read points de-bias by
        ``x / xw``.  Returns (x', q', m', xw', qw', mw').
        """
        key, sr_key = self.sr_split(key, (q, m, x))
        c, wc, cw, wcw = self.exchange_ps(key, x, q, xw, qw, t)
        return self.step_ps_update(c, wc, cw, wcw, x, q, m, v, xw, qw, mw,
                                   gamma, eta, sr_key=sr_key)

    def step_ps_update(self, c, wc, cw, wcw, x, q, m, v, xw, qw, mw,
                       gamma: float, eta: float, sr_key=None):
        """The fused second half of :meth:`step_ps` (no communication).

        The weight-plane update is three (n,)-vector AXPYs -- negligible
        next to the param planes, so it stays plain jnp on every backend,
        and it is *always* f32-exact: compressing or rounding the push-sum
        weight would break the column-mass invariant ``1^T xw = n``.
        """
        x2, q2, m2 = self.step_update(c, wc, x, q, m, v, gamma, eta,
                                      sr_key=sr_key)
        with jax.named_scope("engine.ef_update"):
            qw2 = qw + cw
            mw2 = mw + wcw
            xw2 = (xw + gamma * (mw2 - qw2)).astype(xw.dtype)
            return x2, q2, m2, xw2, qw2, mw2

    def gossip_apply(self, key, y, q, m, gamma: float, scale: float = 1.0,
                     t=None):
        """CHOCO-SGD / SoteriaFL-style round (no tracking term).

        q += scale*c; m += scale*Wc; y' = y + gamma*(m - q).
        Returns (y', q', m').  ``scale`` is the shift stepsize (1 for
        CHOCO, alpha for shifted compression); ``t`` the absolute round
        index for time-varying mixers.
        """
        key, sr_key = self.sr_split(key, (q, m, y))
        c, wc = self.exchange(key, y, q, t)
        with jax.named_scope("engine.ef_update"):
            kw = self._kernel_kw()
            if self._use_pallas():
                qo, mo, yo = self._plane_update(
                    lambda *p, out_dtype=None: ops.ef_gossip(
                        *p, gamma, scale, out_dtype=out_dtype, **kw),
                    (q, m, y, c, wc), sr_key)
                return yo, qo, mo
            if sr_key is not None and any(_sr_dtype(t) for t in (q, m, y)):
                q2f = _tree(lambda a, b: a + scale * b, self._f32(q),
                            self._f32(c))
                m2f = _tree(lambda a, b: a + scale * b, self._f32(m),
                            self._f32(wc))
                y2f = _tree(lambda y0, mm, qq: y0 + gamma * (mm - qq),
                            self._f32(y), m2f, q2f)
                kq, km, ky = jax.random.split(sr_key, 3)
                return (self._sr_writeback(y2f, y, ky),
                        self._sr_writeback(q2f, q, kq),
                        self._sr_writeback(m2f, m, km))
            q2 = _tree(lambda a, b: a + scale * b, q, c)
            m2 = _tree(lambda a, b: a + scale * b, m, wc)
            y2 = _tree(lambda y0, mm, qq: y0 + gamma * (mm - qq), y, m2, q2)
            return y2, q2, m2

    def shift(self, key, y, q, scale: float = 1.0):
        """SoteriaFL shifted compression (mirrorless surrogate accumulate).

        c = C(y - q); q' = q + scale*c.  Returns (c, q') -- the caller owns
        the server-side aggregation of ``c`` (a mean, not a gossip mix).
        """
        c = self.compress(key, self._increment(y, q))
        with jax.named_scope("engine.ef_update"):
            return c, _tree(lambda a, b: (a + scale * b).astype(a.dtype),
                            q, c)

    # -- wire accounting ----------------------------------------------------

    def _packed_windows(self, tree, n_agents: int) -> int:
        """PACK_BLOCK windows the packed executor actually pads for ``tree``.

        ``make_packed_mixer.local`` packs each *leaf* separately and, under
        a sharded layout, runs once per model shard -- so the window count
        is summed per (leaf x model shard), not derived from the
        concatenated element count (which under-reports whenever separate
        pads each round up).  Falls back to unsharded per-leaf counts when
        the engine carries no layout or the specs do not match ``tree``.
        """
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        shard_counts = [1] * len(leaves)
        if self.mesh is not None and self.leaf_specs is not None:
            specs, sdef = jax.tree_util.tree_flatten(
                self.leaf_specs, is_leaf=lambda x: isinstance(x, P))
            if sdef == treedef:
                agent = set(self.agent_axes)

                def nshards(s) -> int:
                    n = 1
                    for entry in tuple(s):
                        if entry is None:
                            continue
                        names = (entry if isinstance(entry, tuple)
                                 else (entry,))
                        for name in names:
                            if name not in agent:
                                n *= int(self.mesh.shape[name])
                    return n

                shard_counts = [nshards(s) if isinstance(s, P) else 1
                                for s in specs]
        total = 0
        for leaf, ns in zip(leaves, shard_counts):
            d_leaf = int(leaf.size) // n_agents
            local = -(-d_leaf // ns)               # per-shard elements
            total += ns * (-(-local // PACK_BLOCK))
        return total

    def _ps_weight_bytes(self, n_agents: int, measured: bool) -> float:
        """Extra bytes the push-sum weight plane puts on the wire per round.

        Each shipped agent buffer set carries one exact f32 weight (4
        bytes): as a flat extra column for dense/ring, as bitcast words
        appended to the last codec buffer.  The multiplier follows each
        mode's link convention (:func:`repro.core.gossip.gossip_wire_bytes`):
        'ring' ships per-agent to its live neighbors (one shift at n=2),
        every other mode ships all n agents' buffers.  For codec mixers the
        measured path traces the weight-word layout off the codec itself
        (:func:`repro.core.wire_formats.measured_weight_nbytes`).
        """
        codec = getattr(self.mixer, "wire_codec", None)
        if codec is not None and measured:
            per = float(WF.measured_weight_nbytes(codec))
        else:
            per = 4.0
        mode = getattr(self.mixer, "wire_mode", "dense")
        if mode == "ring":
            return (1.0 if n_agents == 2 else 2.0) * per
        return float(n_agents) * per

    def wire_bytes(self, tree_or_d, n_agents: Optional[int] = None,
                   push_sum: bool = False) -> float:
        """Model-level bytes crossing agent links per round for one buffer.

        Accepts either an agent-stacked pytree (n and d inferred) or a
        per-agent parameter count ``d`` plus ``n_agents``.  Accounting
        follows the mixer's wire format, with each mode's convention taken
        from :func:`repro.core.gossip.gossip_wire_bytes`: 'ring' exchanges
        dense neighbor increments (2*d floats per agent, n-independent);
        'packed' all-gathers (value, int32 index) pairs; 'dense' emulation
        charges the compressor's own payload (``Compressor.wire_bits``),
        which is n*d floats for identity and k*(value+index) for the
        sparse family -- i.e. the bytes a real deployment of that
        compressor would move.  For 'packed' with a pytree the window
        count is exact per (leaf x model shard) via
        :meth:`_packed_windows` -- the executor pads each leaf (and each
        shard) separately, so ``gossip_wire_bytes``'s single-buffer model
        would under-report; the scalar-``d`` overload keeps the
        single-buffer convention.  Compare algorithms under the *same*
        gossip mode (as benchmarks/ablation.py does); cross-mode numbers
        follow each wire format's own link accounting.

        ``push_sum=True`` accounts a :meth:`exchange_ps` round instead: the
        weight plane's bytes (4 per shipped buffer set, see
        :meth:`_ps_weight_bytes`) are added on top, in both the measured and
        the model path, so ``--achieved-bytes`` parity covers the directed
        codec path too.

        Mixed precision: the dense-neighbor 'ring' payload and the value
        half of 'packed' pairs ship in the engine's ``plane_dtype`` (2
        B/element for bf16 -- what a pytree of bf16 buffers actually puts
        through ``ppermute``/all-gather); indices stay int32 and the
        push-sum weight stays 4-byte f32.  The 'dense' emulation path
        charges ``Compressor.wire_bits`` unchanged -- that model describes
        the compressor's own (f32 value, index) deployment payload, not
        buffers this process ships, so it does not narrow with the planes.
        """
        codec = getattr(self.mixer, "wire_codec", None)
        if codec is not None:
            return self._codec_bytes(tree_or_d, n_agents, measured=True,
                                     push_sum=push_sum)
        tree = None
        if n_agents is None:
            tree = tree_or_d
            leaves = jax.tree_util.tree_leaves(tree)
            n_agents = leaves[0].shape[0]
            d = sum(int(l.size) // n_agents for l in leaves)
        else:
            d = int(tree_or_d)
        db = (float(jnp.dtype(self.plane_dtype).itemsize)
              if self.plane_dtype is not None else 4.0)
        extra = (self._ps_weight_bytes(n_agents, measured=True)
                 if push_sum else 0.0)
        mode = getattr(self.mixer, "wire_mode", "dense")
        if mode in ("ring", "packed"):
            frac = getattr(self.mixer, "wire_frac", None)
            frac = self.compressor.rho if frac is None else frac
            if mode == "packed" and tree is not None:
                k_b = max(int(round(frac * PACK_BLOCK)), 1)
                windows = self._packed_windows(tree, n_agents)
                return (float(n_agents) * windows * k_b * (db + 4.0)
                        + extra)
            return gossip_wire_bytes(mode, n_agents, d, frac=frac,
                                     dtype_bytes=db) + extra
        return n_agents * self.compressor.wire_bits(d) / 8.0 + extra

    def wire_bytes_model(self, tree_or_d, n_agents: Optional[int] = None,
                         push_sum: bool = False) -> float:
        """The *analytic* byte model for the same round (cross-check).

        For codec (bit-packed) mixers this is the layout arithmetic of
        :class:`repro.core.wire_formats.WireFormat` -- windows times
        (payload + overhead) bytes per window -- whereas
        :meth:`wire_bytes` measures the shipped buffers' nbytes from their
        traced shapes; ``bench_comm_round.py --achieved-bytes`` asserts the
        two agree exactly.  For every other mixer the model *is* the
        accounting, so this returns the same value as :meth:`wire_bytes`.
        """
        if getattr(self.mixer, "wire_codec", None) is not None:
            return self._codec_bytes(tree_or_d, n_agents, measured=False,
                                     push_sum=push_sum)
        return self.wire_bytes(tree_or_d, n_agents, push_sum=push_sum)

    def _codec_bytes(self, tree_or_d, n_agents: Optional[int],
                     measured: bool, push_sum: bool = False) -> float:
        """Collective bytes under a codec mixer, measured or modeled.

        Windows are counted per (leaf x model shard) exactly like
        :meth:`_packed_windows` (each shard pads and packs separately);
        per-window bytes come either from ``jax.eval_shape`` over the codec
        itself (measured -- cannot drift from the executor) or from the
        registered layout constants (model).  'ring' ships each agent's
        buffers to its live neighbors (one shift at n=2 by band folding,
        else two); 'packed' all-gathers every agent's buffers.
        """
        codec = self.mixer.wire_codec
        if n_agents is None:
            tree = tree_or_d
            n_agents = jax.tree_util.tree_leaves(tree)[0].shape[0]
            windows = self._packed_windows(tree, n_agents)
        else:
            windows = codec.windows(int(tree_or_d))
        if measured:
            per_window = float(WF.measured_pack_nbytes(codec, PACK_BLOCK))
        else:
            per_window = float(codec.payload_bytes_per_window
                               + codec.overhead_bytes_per_window)
        per_agent = windows * per_window
        if push_sum:
            per_agent += (float(WF.measured_weight_nbytes(codec))
                          if measured else 4.0)
        mode = getattr(self.mixer, "wire_mode", "packed")
        if mode == "ring":
            shifts = 1.0 if n_agents == 2 else 2.0
            return shifts * per_agent
        return float(n_agents) * per_agent
