"""Gossip (neighbor mixing) executors over agent-stacked pytrees.

PORTER communicates *increments*: each round every agent broadcasts
``incr_i = C(y_i - q_i)`` to its neighbors, every agent accumulates its own
surrogate ``q_i += incr_i`` and a *mixing mirror* ``m_i += sum_j w_ij incr_j``,
and the gossip term used by the algorithm is ``(Q (W - I))_i = m_i - q_i``
(exactly, by linearity of the accumulation).  This mirrors what a real
deployment does -- only increments ever hit the wire -- and makes the
collective bytes of the three wire formats directly comparable:

* ``dense``    all-gather of the dense increment   (n * d bytes / round)
               -- the paper's math, zeros included; baseline.
* ``ring``     W is banded on a ring: two ppermute shifts (2 * d bytes),
               independent of n.  Exact for ring topologies.
* ``packed``   all-gather of top-k (values, indices) pairs
               (n * 2k bytes) + local scatter-add.  Exact whenever the
               compressor output is k-sparse (top-k / block-top-k), which is
               how the paper's claimed communication saving is realized on
               the wire.  This is a beyond-paper systems contribution.

All executors compute ``W @ incr`` over the leading agent axis.  The dense
executor is pure einsum and works both in single-device simulation and under
pjit (XLA inserts the all-gather).  ``ring`` and ``packed`` are shard_map
programs and require a mesh.

Time-varying topologies: every factory also accepts a stacked
``(period, n, n)`` table (a :class:`repro.core.mixing.TopologySchedule`'s
``ws``).  The returned mixer then takes the *absolute round index* as a
second, traced argument and gathers ``W_{t mod period}`` from a device copy
of the table inside the compiled program -- one executable serves the whole
schedule, and because the index is the state's own step counter the
trajectory is chunking- and restart-invariant like the PRNG stream.  The
ring fast path keeps its two-ppermute shift structure and only traces the
*band weights* per round (the graph stays a ring; weights rotate), so its
wire bytes stay 2*d regardless of the schedule.  Static mixers ignore the
round index; :func:`apply_mixer` dispatches either way.

Push-sum (directed, column-stochastic W): the dense and ring executors
expose ``mix.push(tree, wvec, t)`` which mixes the scalar push-sum weight
plane (shape (n,)) alongside the params with the *same* W, and the codec
executors expose ``mix.exchange_ps(key, tree, dw, t)`` which ships the
exact f32 weight increment bitcast inside the packed buffers.  In every
case the weight rides inside a collective the executor already issues --
concatenated onto the first leaf's flattened block (dense einsum, ring
ppermute) or appended as bitcast words to the last wire buffer (codec) --
so carrying the weight plane adds 4 bytes per shipped buffer and zero
extra collectives (the compiled-HLO tests pin this).  Weights are never
compressed: the column-mass conservation push-sum de-biasing relies on
(1^T W = 1^T) must hold exactly for the weight recursion.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import wire_formats as WF
from .mixing import Topology, TopologySchedule
# packed wire format selection window: single source of truth is
# wire_formats.PACK_BLOCK (the executors, the kernels, and the byte model
# all import it from there, so none can drift -- the PR-3 bug class).
from .wire_formats import PACK_BLOCK

__all__ = [
    "GossipBudget",
    "MixFn",
    "PACK_BLOCK",
    "apply_mixer",
    "make_dense_mixer",
    "make_ring_mixer",
    "make_packed_mixer",
    "make_ring_codec_mixer",
    "make_packed_codec_mixer",
    "make_mixer",
    "gossip_wire_bytes",
]

# tree of (n, ...) -> tree of (n, ...); time-varying mixers additionally
# take the traced absolute round index (see apply_mixer)
MixFn = Callable[..., object]


@dataclasses.dataclass(frozen=True)
class GossipBudget:
    """Declared collective budget of one gossip executor.

    Every mixer factory attaches one of these as ``mix.budget`` -- the
    executor's *contract* for what its compiled program may ship, declared
    at construction time and enforced against the lowered HLO by the
    collective census in :mod:`repro.analysis.hlo`.

    ``per_leaf`` maps an HLO collective category (``"collective-permute"``,
    ``"all-gather"``, ...) to the maximum number of such ops the executor
    may emit *per gossiped leaf, per comm round*.  The census multiplies by
    the leaf count and the algorithm's declared
    :attr:`repro.core.registry.AlgorithmInfo.comm_rounds` to bound the whole
    step.  Budgets are upper bounds (XLA's combiner passes may merge ops
    below them); categories absent from ``per_leaf`` are *forbidden* -- a
    single op of an unbudgeted category is a violation.

    ``spmd_dependent`` marks executors (dense einsum gossip) whose
    collective schedule is chosen by the SPMD partitioner, not by the
    executor: under a mesh the census reports their counts without
    enforcing, and enforces the zero-collective contract only in the
    unmeshed harness.

    Push-sum transport never changes a budget: the weight plane rides
    inside already-shipped buffers (``mix.push`` / ``mix.exchange_ps`` add
    zero collectives by construction, and the census proves it).
    """

    executor: str
    per_leaf: "dict[str, int]" = dataclasses.field(default_factory=dict)
    spmd_dependent: bool = False
    note: str = ""

    def bound(self, n_leaves: int, comm_rounds: int) -> "dict[str, int]":
        """Per-category op ceiling for a whole compiled step."""
        return {cat: per * n_leaves * comm_rounds
                for cat, per in self.per_leaf.items()}


def apply_mixer(mixer: MixFn, tree, t=None):
    """Invoke ``mixer``, forwarding the round index only when it needs one.

    Static mixers (and ad-hoc test doubles) keep their 1-argument call
    shape; mixers built from a schedule are tagged ``time_varying`` and
    require ``t`` (the algorithm steps pass their state's step counter)."""
    time_varying = getattr(mixer, "time_varying", False)
    if time_varying and t is None:
        raise ValueError(
            "this mixer runs a time-varying topology schedule and needs "
            "the absolute round index (pass t=state.step)")
    with jax.named_scope("engine.mix"):
        return mixer(tree, t) if time_varying else mixer(tree)


def _schedule_table(w) -> Tuple[np.ndarray, bool]:
    """Normalize ``w`` to a numpy table; True when it is a (p, n, n) stack."""
    w = np.asarray(w, dtype=np.float64)
    if w.ndim == 2:
        return w, False
    if w.ndim == 3:
        return w, True
    raise ValueError(f"mixing matrix must be (n, n) or (period, n, n); got "
                     f"shape {w.shape}")


def _entry(table: jax.Array, t) -> jax.Array:
    """W_t from a stacked device table, traced-index safe."""
    return table[jnp.mod(jnp.asarray(t, jnp.int32), table.shape[0])]


def _einsum_w(w: jax.Array, leaf: jax.Array) -> jax.Array:
    out = jnp.einsum("ij,j...->i...", w.astype(jnp.float32),
                     leaf.astype(jnp.float32))
    return out.astype(leaf.dtype)


def make_dense_mixer(w) -> MixFn:
    """W @ incr via einsum over the agent axis (all-gather under pjit).

    ``w``: (n, n) static matrix, or a stacked (period, n, n) schedule table
    -- the mixer then indexes it with the traced round argument.

    Push-sum: ``mix.push(tree, wvec, t)`` additionally mixes the scalar
    push-sum weight plane ``wvec`` (shape (n,)) with the *same* W.  The
    weight rides as one extra column concatenated onto the first leaf's
    flattened agent block, so the einsum count -- and under pjit the
    collective count -- is identical to the plain call; for f32 leaves the
    param output is bit-identical to ``mix(tree, t)``.
    """
    w_np, time_varying = _schedule_table(w)
    w_j = jnp.asarray(w_np, dtype=jnp.float32)

    if time_varying:
        def mix(tree, t):
            w_t = _entry(w_j, t)
            return jax.tree_util.tree_map(lambda l: _einsum_w(w_t, l), tree)
    else:
        def mix(tree, t=None):
            del t  # static
            return jax.tree_util.tree_map(lambda l: _einsum_w(w_j, l), tree)

    def push(tree, wvec, t=None):
        if time_varying and t is None:
            raise ValueError("time-varying dense mixer needs the round "
                             "index (pass t=state.step)")
        w_t = _entry(w_j, t) if time_varying else w_j
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        l0 = leaves[0]
        flat0 = l0.reshape(l0.shape[0], -1).astype(jnp.float32)
        aug = jnp.concatenate(
            [flat0, wvec.astype(jnp.float32)[:, None]], axis=1)
        aug_m = jnp.einsum("ij,jd->id", w_t.astype(jnp.float32), aug)
        out0 = aug_m[:, :-1].reshape(l0.shape).astype(l0.dtype)
        w_m = aug_m[:, -1].astype(wvec.dtype)
        rest = [_einsum_w(w_t, l) for l in leaves[1:]]
        return treedef.unflatten([out0] + rest), w_m

    mix.push = push
    mix.time_varying = time_varying
    mix.budget = GossipBudget(
        executor="dense", per_leaf={}, spmd_dependent=True,
        note="einsum over the agent axis; unmeshed it emits zero "
             "collectives, under pjit the SPMD partitioner chooses them")
    return mix


# ---------------------------------------------------------------------------
# Ring mixer: two ppermutes; supports the multi-pod ('pod','data') agent grid.
# ---------------------------------------------------------------------------

def _ring_weights(w: np.ndarray) -> Tuple[float, float, float]:
    """Extract (w_self, w_prev, w_next) from a circulant ring mixing matrix.

    At ``n == 2`` the two off-diagonal bands coincide: both ppermute shifts
    deliver the *same* (only) neighbor, so summing a prev and a next term
    would double-count it (``w_self*x + 2*w01*nb``, row sum != 1).  The whole
    neighbor weight is therefore folded into ``w_prev`` and ``w_next`` is
    zeroed, collapsing the executor to a single shift term.  The structure
    check accumulates band weights instead of assigning them, so coinciding
    positions can no longer mask a mismatch (``ref[0, 1]`` used to be
    silently overwritten).
    """
    n = w.shape[0]
    if n < 2:
        raise ValueError("ring gossip needs at least 2 agents; "
                         "use dense gossip for a single agent")
    w_self = float(w[0, 0])
    w_next = float(w[0, 1 % n])
    w_prev = float(w[0, (n - 1) % n])
    if n == 2:
        w_prev, w_next = float(w[0, 1]), 0.0
    # verify circulant-banded structure (accumulate: at n=2 both bands land
    # on the same entry, and with w_next folded to 0 the sum is exact)
    ref = np.zeros_like(w)
    for i in range(n):
        ref[i, i] += w_self
        ref[i, (i + 1) % n] += w_next
        ref[i, (i - 1) % n] += w_prev
    if not np.allclose(ref, w, atol=1e-10):
        raise ValueError("mixing matrix is not a circulant ring band; "
                         "use dense or packed gossip")
    return w_self, w_prev, w_next


def make_ring_mixer(w, mesh: Mesh,
                    agent_axes: Sequence[str] = ("data",),
                    leaf_specs=None) -> MixFn:
    """Banded-W gossip via lax.ppermute (wire bytes: 2*d, n-independent).

    For the multi-pod agent grid the logical agent index is
    pod * data_size + data; shifts that cross the pod boundary are patched
    with an extra ppermute over the 'pod' axis.

    ``w`` may be a stacked (period, n, n) schedule table; every round must
    then be a circulant ring band.  The *shift structure* stays static --
    which bands are ever nonzero across the window decides which ppermutes
    the program emits -- and only the three band weights are traced
    (gathered per round from a (period, 3) device table), so the compiled
    collective schedule and the 2*d wire accounting are schedule-invariant.
    """
    w_np, time_varying = _schedule_table(w)
    if time_varying:
        band_tab = np.stack([_ring_weights(wt) for wt in w_np])  # (p, 3)
        use_prev = bool(np.any(band_tab[:, 1] != 0.0))
        use_next = bool(np.any(band_tab[:, 2] != 0.0))
        bands_j = jnp.asarray(band_tab, jnp.float32)
    else:
        w_self, w_prev, w_next = _ring_weights(w_np)
        use_prev, use_next = bool(w_prev), bool(w_next)
    axes = tuple(agent_axes)

    def shift(x, direction: int, axis: str):
        size = mesh.shape[axis]
        perm = [(i, (i + direction) % size) for i in range(size)]
        if x.dtype == jnp.bfloat16:
            # ship the u16 bit pattern, like the codec executors: XLA's
            # float normalization (CPU has no native bf16) widens bf16
            # compute *and its collectives* to f32, silently doubling the
            # wire; integer collectives are never normalized, so the
            # bitcast pins bf16 planes at 2 B/elem
            raw = jax.lax.ppermute(
                jax.lax.bitcast_convert_type(x, jnp.uint16), axis, perm)
            return jax.lax.bitcast_convert_type(raw, jnp.bfloat16)
        return jax.lax.ppermute(x, axis, perm)

    def banded_copies(x):
        """Shifted copies of ``x`` paired with their band slot (0=self,
        1=prev, 2=next), in the accumulation order ``local`` uses.

        Zero-weight bands send nothing (n=2 ring folds everything into
        w_prev; its second ppermute would be a dead wire transfer);
        use_prev/use_next are static over the whole schedule window.  The
        shifts move x in its own dtype (bf16 planes ship 2 B/elem).
        """
        if len(axes) == 1:
            ax = axes[0]
            cps = [(0, x)]
            if use_prev:
                cps.append((1, shift(x, +1, ax)))  # agent i-1 arrives at i
            if use_next:
                cps.append((2, shift(x, -1, ax)))
            return cps
        pod_ax, data_ax = axes
        dsize = mesh.shape[data_ax]
        didx = jax.lax.axis_index(data_ax)
        cps = [(0, x)]
        # intra-pod shifted copies (wrap inside the pod is wrong at the seam);
        # seam fix: data==0 must receive pod-1's last agent; data==dsize-1
        # must receive pod+1's first agent.
        if use_prev:
            prev_intra = shift(x, +1, data_ax)
            prev_cross = shift(prev_intra, +1, pod_ax)
            cps.append((1, jnp.where(didx == 0, prev_cross, prev_intra)))
        if use_next:
            next_intra = shift(x, -1, data_ax)
            next_cross = shift(next_intra, -1, pod_ax)
            cps.append((2, jnp.where(didx == dsize - 1, next_cross,
                                     next_intra)))
        return cps

    def local(x, b_self, b_prev, b_next):  # x: (1, ...) local agent block
        # the band weights are traced f32 scalars under a schedule, so the
        # weighted sum promotes -- cast back so W @ x keeps x's dtype
        bands = (b_self, b_prev, b_next)
        out = None
        for i, cp in banded_copies(x):
            term = bands[i] * cp
            out = term if out is None else out + term
        return out.astype(x.dtype)

    def mix(tree, t=None):
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        if time_varying:
            if t is None:
                raise ValueError("time-varying ring mixer needs the round "
                                 "index (pass t=state.step)")
            b = _entry(bands_j, t)  # (3,) replicated, traced per round
            fn = jax.shard_map(
                lambda tr, bb: jax.tree_util.tree_map(
                    lambda l: local(l, bb[0], bb[1], bb[2]), tr),
                mesh=mesh, in_specs=(specs, P()), out_specs=specs,
                check_vma=False)
            return fn(tree, b)
        fn = jax.shard_map(
            lambda tr: jax.tree_util.tree_map(
                lambda l: local(l, w_self, w_prev, w_next), tr),
            mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False)
        return fn(tree)

    def push(tree, wvec, t=None):
        """Push-sum ring gossip: mix ``tree`` and the (n,) weight plane
        ``wvec`` with the same banded W.  The weight scalar is concatenated
        onto the first leaf's flattened local block before the shifts, so
        the ppermute count is identical to the plain call (the weight adds
        4 wire bytes per shipped block, no extra collective)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        w_spec = P(axes if len(axes) > 1 else axes[0])
        if time_varying:
            if t is None:
                raise ValueError("time-varying ring mixer needs the round "
                                 "index (pass t=state.step)")
            b = _entry(bands_j, t)
        else:
            b = jnp.asarray([w_self, w_prev, w_next], jnp.float32)

        def run(lvs, wv, bb):
            # The exact f32 weight word rides as bitcast lanes of the
            # payload dtype (1 lane beside f32 planes, 2 beside bf16), so
            # one ppermute per band still carries payload + weight and a
            # bf16 plane keeps its 2 B/elem wire.  Mixing happens on the
            # *split* halves -- payload accumulated in f32 and cast back,
            # weight bitcast back to f32 and mixed exactly -- which is
            # elementwise identical to concatenating in f32 throughout
            # (bit-exact for legacy f32 planes).
            l0 = lvs[0]
            flat0 = l0.reshape(1, -1)
            d0 = flat0.shape[1]
            nl = 4 // jnp.dtype(l0.dtype).itemsize
            wword = jax.lax.bitcast_convert_type(
                wv.astype(jnp.float32).reshape(1, 1),
                l0.dtype).reshape(1, nl)
            aug = jnp.concatenate([flat0, wword], axis=1)
            out0 = w_m = None
            for i, cp in banded_copies(aug):
                pay = bb[i] * cp[:, :d0].astype(jnp.float32)
                wgt = bb[i] * jax.lax.bitcast_convert_type(
                    cp[:, d0:], jnp.float32).reshape(())
                out0 = pay if out0 is None else out0 + pay
                w_m = wgt if w_m is None else w_m + wgt
            out0 = out0.reshape(l0.shape).astype(l0.dtype)
            w_m = w_m.reshape(wv.shape).astype(wv.dtype)
            rest = [local(l, bb[0], bb[1], bb[2]) for l in lvs[1:]]
            return [out0] + rest, w_m

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(spec_leaves, w_spec, P()),
                           out_specs=(spec_leaves, w_spec), check_vma=False)
        outs, w_m = fn(leaves, wvec, b)
        return treedef.unflatten(outs), w_m

    mix.push = push
    mix.time_varying = time_varying
    # one ppermute per live band; the multi-pod seam patch doubles it (an
    # extra shift over the 'pod' axis); n=2 folding halves it (use_next=0)
    _shifts = int(use_prev) + int(use_next)
    mix.budget = GossipBudget(
        executor="ring",
        per_leaf={"collective-permute":
                  _shifts * (2 if len(axes) == 2 else 1)},
        note=f"{_shifts} live band(s) x "
             f"{2 if len(axes) == 2 else 1} agent axis(es); "
             "push-sum weight rides in leaf 0, zero extra")
    return mix


# ---------------------------------------------------------------------------
# Packed top-k mixer: all-gather (values, indices) only.
# ---------------------------------------------------------------------------

def make_packed_mixer(w, mesh: Mesh, frac: float,
                      agent_axes: Sequence[str] = ("data",),
                      leaf_specs=None) -> MixFn:
    """W @ incr where only top-k (values, int32 indices) cross the wire.

    Exact when ``incr`` is k-sparse per agent (top-k / block-top-k
    compressors); otherwise it *re-compresses* the increment, which composes
    two rho-contractions and is still a valid compressor (documented).

    Each leaf may additionally be sharded over the 'model' axis; packing then
    selects top-k *per model shard* (block top-k across shards), keeping the
    collective strictly within the agent axes.

    ``w`` may be a stacked (period, n, n) schedule table; the round's W is
    gathered outside the shard_map body and enters it through the same
    replicated-argument slot the static matrix already used, so the wire
    payload (packed pairs only) is schedule-invariant.
    """
    w_np, time_varying = _schedule_table(w)
    w_np = w_np.astype(np.float32)
    n = w_np.shape[-1]
    axes = tuple(agent_axes)
    gather_axis = axes if len(axes) > 1 else axes[0]

    block = PACK_BLOCK  # selection window; matches kernels/block_topk.py

    def local(x, w_col):
        # x: (1, ...) local agent's increment block (possibly model-sharded).
        # Pack per 2048-elem window (the block-top-k wire format): top_k stays
        # int32-safe and cheap even on multi-billion-element expert leaves.
        flat = x.reshape(-1)
        d = flat.shape[0]
        pad = (-d) % block
        rows = jnp.pad(flat, (0, pad)).reshape(-1, block)   # (nb, block)
        nb = rows.shape[0]
        k_b = max(int(round(frac * block)), 1)
        vals_abs, idx = jax.lax.top_k(jnp.abs(rows), k_b)   # (nb, k_b)
        del vals_abs
        vals = jnp.take_along_axis(rows, idx, axis=1)
        # gather every agent's packed increment: (n, nb, k_b) each.  bf16
        # values gather as their u16 bit pattern, like the codec
        # executors: XLA's float normalization (no native bf16 on CPU)
        # widens bf16 collectives to f32, silently doubling the wire;
        # integer collectives are never normalized.
        if vals.dtype == jnp.bfloat16:
            all_vals = jax.lax.bitcast_convert_type(
                jax.lax.all_gather(
                    jax.lax.bitcast_convert_type(vals, jnp.uint16),
                    gather_axis),
                jnp.bfloat16).reshape(n, nb, k_b)
        else:
            all_vals = jax.lax.all_gather(vals, gather_axis
                                          ).reshape(n, nb, k_b)
        all_idx = jax.lax.all_gather(idx.astype(jnp.int32),
                                     gather_axis).reshape(n, nb, k_b)
        # weighted per-row scatter-add: sum_j w_ij * unpack(incr_j).
        # The gathered values cross the wire in x's dtype (2 B/elem for
        # bf16 planes); the receive-side accumulation runs in f32 and casts
        # back, so mixing never widens the resident buffer.
        weighted = (all_vals.astype(jnp.float32)
                    * w_col.astype(jnp.float32)[:, None, None])  # (n, nb, k_b)
        out = jnp.zeros((nb, block), jnp.float32)
        row_ids = jnp.arange(nb)[:, None]

        def add_agent(o, j):
            return o.at[row_ids, all_idx[j]].add(weighted[j]), None

        out, _ = jax.lax.scan(add_agent, out, jnp.arange(n))
        return out.reshape(-1)[:d].reshape(x.shape).astype(x.dtype)

    w_j = jnp.asarray(w_np)  # (n, n) or (period, n, n)

    def mix(tree, t=None):
        if time_varying:
            if t is None:
                raise ValueError("time-varying packed mixer needs the round "
                                 "index (pass t=state.step)")
            w_rows = _entry(w_j, t)  # (n, n), traced per round
        else:
            w_rows = w_j

        def run(tr, w_all):
            if len(axes) == 1:
                i = jax.lax.axis_index(axes[0])
            else:
                i = (jax.lax.axis_index(axes[0]) * mesh.shape[axes[1]]
                     + jax.lax.axis_index(axes[1]))
            row = w_all[i]
            return jax.tree_util.tree_map(lambda l: local(l, row), tr)

        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(specs, P()), out_specs=specs,
                           check_vma=False)
        return fn(tree, w_rows)

    mix.time_varying = time_varying
    mix.budget = GossipBudget(
        executor="packed", per_leaf={"all-gather": 2},
        note="one all-gather each for the (values, indices) planes")
    return mix


# ---------------------------------------------------------------------------
# Codec-aware executors: only bit-packed buffers ever cross the wire.
#
# Unlike the mixers above (dense increment in, mixed increment out), a codec
# executor *fuses compression with packing*: it takes the raw increment
# ``delta = y - q``, packs it per PACK_BLOCK window into the wire buffers of
# a :class:`repro.core.wire_formats.WireFormat`, ships only those buffers
# (ppermute for ring, all-gather for packed), and unpacks on the receiver.
# It returns BOTH ``c = unpack(pack(delta))`` (the locally round-tripped
# increment every agent accumulates into its surrogate q) and ``wc = W c``
# -- the two must come from the *same* packed buffers or the ``m = W q``
# invariant breaks, which is why the codec path replaces the engine's
# separate compress step rather than composing with it.  Drive these
# through ``mix.exchange(key, tree, t)`` (CommRound does); the plain call
# raises.
# ---------------------------------------------------------------------------

def _codec_mix_error(*a, **k):
    raise ValueError(
        "codec gossip executors fuse compression with packing and return "
        "(c, wc); call mix.exchange(key, tree, t) -- the CommRound engine "
        "does this -- instead of mixing a pre-compressed tree")


def _agent_index(mesh: Mesh, axes: Tuple[str, ...]):
    if len(axes) == 1:
        return jax.lax.axis_index(axes[0])
    return (jax.lax.axis_index(axes[0]) * mesh.shape[axes[1]]
            + jax.lax.axis_index(axes[1]))


def _pack_local(codec: WF.WireFormat, key, x):
    """Pack one (1, ...) local block: returns (bufs, c_rows, d)."""
    with jax.named_scope("engine.compress"):
        flat = x.reshape(-1).astype(jnp.float32)
        rows = WF.to_windows(flat)
        bufs = codec.pack(key, rows)
        return bufs, codec.unpack(*bufs), flat.shape[0]


# Wire armor: float wire buffers are bitcast to same-width uints for the
# collective itself.  Without this, XLA's convert-mover is free to hoist
# the receiver-side f32 upcast across the collective (the CPU backend does
# not model comm cost), silently shipping the bf16 value plane -- or the
# qsgd scale column -- as dense f32.  A bitcast is a hard boundary no
# convert can cross, and the round trip is bit-exact.

_ARMOR_UINT = {2: jnp.uint16, 4: jnp.uint32}


def _armor_bufs(bufs):
    """Bitcast float buffers to uint for shipping -> (armored, orig dtypes)."""
    out, kinds = [], []
    for b in bufs:
        # issubdtype, not dtype.kind: ml_dtypes' bfloat16 reports kind 'V'
        if jnp.issubdtype(b.dtype, jnp.floating):
            u = _ARMOR_UINT[jnp.dtype(b.dtype).itemsize]
            out.append(jax.lax.bitcast_convert_type(b, u))
            kinds.append(b.dtype)
        else:
            out.append(b)
            kinds.append(None)
    return tuple(out), tuple(kinds)


def _unarmor_bufs(bufs, kinds):
    """Inverse of :func:`_armor_bufs` on the received buffers."""
    return tuple(jax.lax.bitcast_convert_type(b, k) if k is not None else b
                 for b, k in zip(bufs, kinds))


# Push-sum weight transport for codec executors: the exact (uncompressed)
# f32 weight increment is bitcast into words of the last wire buffer's
# dtype and appended to its flattened payload -- +4 bytes per shipped
# buffer, zero extra collectives.  Bitcasting (not casting) keeps the
# transport exact: the receiver recovers the identical f32 bits.

def _weight_word_count(dtype) -> int:
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4):
        raise ValueError(f"cannot bitcast an f32 push-sum weight into "
                         f"{jnp.dtype(dtype)} wire words")
    return 4 // itemsize


def _append_weight(bufs, wloc):
    """(bufs, (1,) weight) -> (shipped bufs, original last-buffer shape)."""
    last = bufs[-1]
    w32 = jax.lax.bitcast_convert_type(
        wloc.astype(jnp.float32).reshape(1), jnp.uint32)
    if jnp.dtype(last.dtype).itemsize == 4:
        words = w32
    else:
        words = jax.lax.bitcast_convert_type(w32, jnp.uint16).reshape(-1)
    if words.dtype != last.dtype:
        words = jax.lax.bitcast_convert_type(words, last.dtype)
    return tuple(bufs[:-1]) + (jnp.concatenate([last.reshape(-1), words]),), \
        last.shape


def _split_weight(bufs, last_shape):
    """Inverse of :func:`_append_weight`: -> (original bufs, f32 weight)."""
    last = bufs[-1]
    nw = _weight_word_count(last.dtype)
    words = last[last.shape[0] - nw:]
    orig = last[:last.shape[0] - nw].reshape(last_shape)
    if jnp.dtype(words.dtype).itemsize == 2:
        words = jax.lax.bitcast_convert_type(words, jnp.uint16)
        w32 = jax.lax.bitcast_convert_type(words, jnp.uint32)
    else:
        w32 = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(-1)[:1]
    w32 = w32.reshape(())
    return tuple(bufs[:-1]) + (orig,), \
        jax.lax.bitcast_convert_type(w32, jnp.float32)


def make_ring_codec_mixer(w, mesh: Mesh, codec: WF.WireFormat,
                          agent_axes: Sequence[str] = ("data",),
                          leaf_specs=None) -> MixFn:
    """Banded-W gossip that ppermutes *packed* buffers (bf16+u16 segments or
    uint32 code words) instead of dense f32 planes.  Keeps the two-shift
    structure, the n=2 band folding, the multi-pod seam patch, and the
    traced (period, 3) band table of :func:`make_ring_mixer`; the receiver
    unpacks each neighbor's buffers before applying its band weight."""
    w_np, time_varying = _schedule_table(w)
    if time_varying:
        band_tab = np.stack([_ring_weights(wt) for wt in w_np])  # (p, 3)
        use_prev = bool(np.any(band_tab[:, 1] != 0.0))
        use_next = bool(np.any(band_tab[:, 2] != 0.0))
        bands_j = jnp.asarray(band_tab, jnp.float32)
    else:
        w_self, w_prev, w_next = _ring_weights(w_np)
        use_prev, use_next = bool(w_prev), bool(w_next)
    axes = tuple(agent_axes)

    def shift_bufs(bufs, direction: int, axis: str):
        size = mesh.shape[axis]
        perm = [(i, (i + direction) % size) for i in range(size)]
        armored, kinds = _armor_bufs(bufs)
        shipped = tuple(jax.lax.ppermute(b, axis, perm) for b in armored)
        return _unarmor_bufs(shipped, kinds)

    def local(x, b_self, b_prev, b_next, key):
        bufs, c_rows, d = _pack_local(codec, key, x)
        out = b_self * c_rows
        if len(axes) == 1:
            ax = axes[0]
            if use_prev:
                out = out + b_prev * codec.unpack(
                    *shift_bufs(bufs, +1, ax))   # agent i-1 arrives at i
            if use_next:
                out = out + b_next * codec.unpack(*shift_bufs(bufs, -1, ax))
        else:
            pod_ax, data_ax = axes
            dsize = mesh.shape[data_ax]
            didx = jax.lax.axis_index(data_ax)
            # seam fix as in make_ring_mixer, applied per wire buffer (all
            # agents' buffers share shapes, so the select is element-free)
            if use_prev:
                intra = shift_bufs(bufs, +1, data_ax)
                cross = shift_bufs(intra, +1, pod_ax)
                sel = tuple(jnp.where(didx == 0, c, i_)
                            for c, i_ in zip(cross, intra))
                out = out + b_prev * codec.unpack(*sel)
            if use_next:
                intra = shift_bufs(bufs, -1, data_ax)
                cross = shift_bufs(intra, -1, pod_ax)
                sel = tuple(jnp.where(didx == dsize - 1, c, i_)
                            for c, i_ in zip(cross, intra))
                out = out + b_next * codec.unpack(*sel)
        to_leaf = lambda rows: WF.from_windows(rows, d, x.shape
                                               ).astype(x.dtype)
        return to_leaf(c_rows), to_leaf(out)

    def exchange(key, tree, t=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))

        if time_varying:
            if t is None:
                raise ValueError("time-varying ring codec mixer needs the "
                                 "round index (pass t=state.step)")
            b = _entry(bands_j, t)
        else:
            b = jnp.asarray([w_self, w_prev, w_next], jnp.float32)

        def run(lvs, ks, bb):
            i = _agent_index(mesh, axes)
            outs = [local(l, bb[0], bb[1], bb[2],
                          jax.random.fold_in(ks[j], i))
                    for j, l in enumerate(lvs)]
            return [o[0] for o in outs], [o[1] for o in outs]

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(spec_leaves, P(), P()),
                           out_specs=(spec_leaves, spec_leaves),
                           check_vma=False)
        cs, wcs = fn(leaves, keys, b)
        return treedef.unflatten(cs), treedef.unflatten(wcs)

    def local_ps(x, b_self, b_prev, b_next, wloc, key):
        """Leaf-0 variant of ``local``: the agent's exact f32 weight
        increment rides bitcast inside the shipped buffers (+4 bytes, no
        extra ppermute); returns (c, wc, cw, wcw) local blocks."""
        bufs, c_rows, d = _pack_local(codec, key, x)
        ship, last_shape = _append_weight(bufs, wloc)
        w_loc = wloc.astype(jnp.float32).reshape(())
        out = b_self * c_rows
        w_out = b_self * w_loc

        def absorb(shipped, band):
            nonlocal out, w_out
            orig, wj = _split_weight(shipped, last_shape)
            out = out + band * codec.unpack(*orig)
            w_out = w_out + band * wj

        if len(axes) == 1:
            ax = axes[0]
            if use_prev:
                absorb(shift_bufs(ship, +1, ax), b_prev)
            if use_next:
                absorb(shift_bufs(ship, -1, ax), b_next)
        else:
            pod_ax, data_ax = axes
            dsize = mesh.shape[data_ax]
            didx = jax.lax.axis_index(data_ax)
            if use_prev:
                intra = shift_bufs(ship, +1, data_ax)
                cross = shift_bufs(intra, +1, pod_ax)
                absorb(tuple(jnp.where(didx == 0, c, i_)
                             for c, i_ in zip(cross, intra)), b_prev)
            if use_next:
                intra = shift_bufs(ship, -1, data_ax)
                cross = shift_bufs(intra, -1, pod_ax)
                absorb(tuple(jnp.where(didx == dsize - 1, c, i_)
                             for c, i_ in zip(cross, intra)), b_next)
        to_leaf = lambda rows: WF.from_windows(rows, d, x.shape
                                               ).astype(x.dtype)
        return (to_leaf(c_rows), to_leaf(out),
                w_loc.reshape(wloc.shape), w_out.reshape(wloc.shape))

    def exchange_ps(key, tree, dw, t=None):
        """Push-sum exchange: like ``exchange`` plus the exact (n,) weight
        increment ``dw``, shipped inside leaf 0's packed buffers.  Returns
        (c, wc, cw, wcw); cw == dw exactly (weights are never compressed,
        else the column-mass invariant breaks)."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        w_spec = P(axes if len(axes) > 1 else axes[0])

        if time_varying:
            if t is None:
                raise ValueError("time-varying ring codec mixer needs the "
                                 "round index (pass t=state.step)")
            b = _entry(bands_j, t)
        else:
            b = jnp.asarray([w_self, w_prev, w_next], jnp.float32)

        def run(lvs, wv, ks, bb):
            i = _agent_index(mesh, axes)
            c0, wc0, cw, wcw = local_ps(lvs[0], bb[0], bb[1], bb[2], wv,
                                        jax.random.fold_in(ks[0], i))
            rest = [local(l, bb[0], bb[1], bb[2],
                          jax.random.fold_in(ks[j], i))
                    for j, l in enumerate(lvs[1:], start=1)]
            return ([c0] + [o[0] for o in rest],
                    [wc0] + [o[1] for o in rest], cw, wcw)

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(spec_leaves, w_spec, P(), P()),
                           out_specs=(spec_leaves, spec_leaves, w_spec,
                                      w_spec),
                           check_vma=False)
        cs, wcs, cw, wcw = fn(leaves, dw, keys, b)
        return (treedef.unflatten(cs), treedef.unflatten(wcs),
                cw.astype(dw.dtype), wcw.astype(dw.dtype))

    def mix(*a, **k):                      # fresh object per factory call
        _codec_mix_error()

    mix.exchange = exchange
    mix.exchange_ps = exchange_ps
    mix.time_varying = time_varying
    mix.wire_codec = codec
    _shifts = int(use_prev) + int(use_next)
    mix.budget = GossipBudget(
        executor="ring_codec",
        per_leaf={"collective-permute":
                  _shifts * (2 if len(axes) == 2 else 1) * codec.n_buffers},
        note=f"{codec.name}: each live band ships {codec.n_buffers} packed "
             "buffers; exchange_ps bitcasts the weight into the last one "
             "(zero extra)")
    return mix


def make_packed_codec_mixer(w, mesh: Mesh, codec: WF.WireFormat,
                            agent_axes: Sequence[str] = ("data",),
                            leaf_specs=None) -> MixFn:
    """All-gather gossip over *packed* buffers: every agent ships its
    bit-packed windows, the receiver unpacks each sender's buffers and
    accumulates ``sum_j w_ij unpack(bufs_j)`` in a scan.  Per-shard planes
    (model-sharded leaves pack per shard) and the traced-``W_t`` schedule
    slot of :func:`make_packed_mixer` are preserved."""
    w_np, time_varying = _schedule_table(w)
    w_np = w_np.astype(np.float32)
    n = w_np.shape[-1]
    axes = tuple(agent_axes)
    gather_axis = axes if len(axes) > 1 else axes[0]
    w_j = jnp.asarray(w_np)

    def gather_bufs(bufs):
        armored, kinds = _armor_bufs(bufs)
        gathered = tuple(
            jax.lax.all_gather(b, gather_axis).reshape(n, *b.shape)
            for b in armored)
        return _unarmor_bufs(gathered, kinds)

    def local(x, w_col, key):
        bufs, c_rows, d = _pack_local(codec, key, x)
        all_bufs = gather_bufs(bufs)

        def add_agent(o, j):
            return o + w_col[j] * codec.unpack(*[ab[j] for ab in all_bufs]
                                               ), None

        out, _ = jax.lax.scan(add_agent, jnp.zeros_like(c_rows),
                              jnp.arange(n))
        to_leaf = lambda rows: WF.from_windows(rows, d, x.shape
                                               ).astype(x.dtype)
        return to_leaf(c_rows), to_leaf(out)

    def exchange(key, tree, t=None):
        if time_varying:
            if t is None:
                raise ValueError("time-varying packed codec mixer needs the "
                                 "round index (pass t=state.step)")
            w_rows = _entry(w_j, t)
        else:
            w_rows = w_j
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))

        def run(lvs, w_all, ks):
            i = _agent_index(mesh, axes)
            row = w_all[i]
            outs = [local(l, row, jax.random.fold_in(ks[j], i))
                    for j, l in enumerate(lvs)]
            return [o[0] for o in outs], [o[1] for o in outs]

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(spec_leaves, P(), P()),
                           out_specs=(spec_leaves, spec_leaves),
                           check_vma=False)
        cs, wcs = fn(leaves, w_rows, keys)
        return treedef.unflatten(cs), treedef.unflatten(wcs)

    def local_ps(x, w_col, wloc, key):
        """Leaf-0 variant of ``local``: the exact f32 weight increment is
        bitcast into the shipped buffers (+4 bytes in the all-gather, no
        extra collective); returns (c, wc, cw, wcw) local blocks."""
        bufs, c_rows, d = _pack_local(codec, key, x)
        ship, last_shape = _append_weight(bufs, wloc)
        all_bufs = gather_bufs(ship)

        def add_agent(carry, j):
            o, wacc = carry
            orig, wj = _split_weight(tuple(ab[j] for ab in all_bufs),
                                     last_shape)
            return (o + w_col[j] * codec.unpack(*orig),
                    wacc + w_col[j] * wj), None

        (out, w_out), _ = jax.lax.scan(
            add_agent, (jnp.zeros_like(c_rows), jnp.zeros((), jnp.float32)),
            jnp.arange(n))
        to_leaf = lambda rows: WF.from_windows(rows, d, x.shape
                                               ).astype(x.dtype)
        return (to_leaf(c_rows), to_leaf(out),
                wloc.astype(jnp.float32),
                w_out.reshape(wloc.shape))

    def exchange_ps(key, tree, dw, t=None):
        """Push-sum exchange: like ``exchange`` plus the exact (n,) weight
        increment ``dw``, shipped inside leaf 0's packed buffers.  Returns
        (c, wc, cw, wcw); cw == dw exactly."""
        if time_varying:
            if t is None:
                raise ValueError("time-varying packed codec mixer needs the "
                                 "round index (pass t=state.step)")
            w_rows = _entry(w_j, t)
        else:
            w_rows = w_j
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        if leaf_specs is not None:
            specs = leaf_specs
        else:
            specs = jax.tree_util.tree_map(
                lambda l: P(axes if len(axes) > 1 else axes[0],
                            *([None] * (l.ndim - 1))), tree)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        w_spec = P(axes if len(axes) > 1 else axes[0])

        def run(lvs, wv, w_all, ks):
            i = _agent_index(mesh, axes)
            row = w_all[i]
            c0, wc0, cw, wcw = local_ps(lvs[0], row, wv,
                                        jax.random.fold_in(ks[0], i))
            rest = [local(l, row, jax.random.fold_in(ks[j], i))
                    for j, l in enumerate(lvs[1:], start=1)]
            return ([c0] + [o[0] for o in rest],
                    [wc0] + [o[1] for o in rest], cw, wcw)

        fn = jax.shard_map(run, mesh=mesh,
                           in_specs=(spec_leaves, w_spec, P(), P()),
                           out_specs=(spec_leaves, spec_leaves, w_spec,
                                      w_spec),
                           check_vma=False)
        cs, wcs, cw, wcw = fn(leaves, dw, w_rows, keys)
        return (treedef.unflatten(cs), treedef.unflatten(wcs),
                cw.astype(dw.dtype), wcw.astype(dw.dtype))

    def mix(*a, **k):
        _codec_mix_error()

    mix.exchange = exchange
    mix.exchange_ps = exchange_ps
    mix.time_varying = time_varying
    mix.wire_codec = codec
    mix.budget = GossipBudget(
        executor="packed_codec", per_leaf={"all-gather": codec.n_buffers},
        note=f"{codec.name}: one all-gather per packed buffer; "
             "exchange_ps bitcasts the weight into the last one (zero "
             "extra)")
    return mix


def make_mixer(topology: Union[Topology, TopologySchedule],
               mode: str = "dense",
               mesh: Optional[Mesh] = None, frac: Optional[float] = None,
               agent_axes: Sequence[str] = ("data",),
               leaf_specs=None, codec: Optional[WF.WireFormat] = None) -> MixFn:
    """leaf_specs: optional pytree of PartitionSpecs matching the gossiped
    buffers (agent axis first, model-parallel dims preserved) -- required for
    ring/packed under a mesh whose leaves are also model-sharded.

    ``topology`` may be a static :class:`Topology` or a time-varying
    :class:`TopologySchedule`; a schedule hands the executor its stacked
    ``(period, n, n)`` table, and the mixer is tagged ``time_varying`` so
    callers (the comm-round engine, dsgd) route the round index to it via
    :func:`apply_mixer`.

    The returned MixFn is tagged with ``wire_mode`` (and ``wire_frac`` for
    packed) so the comm-round engine can account per-round wire bytes
    without being told the gossip mode twice.

    ``codec``: optional :class:`repro.core.wire_formats.WireFormat`; with a
    codec the ring / packed executor becomes the bit-packed variant (only
    packed buffers cross the wire; drive it via ``mix.exchange``).  Dense
    gossip has no codec form -- its whole point is shipping the dense
    emulation the convergence math sees."""
    schedule = topology if isinstance(topology, TopologySchedule) else None
    w = schedule.ws if schedule is not None else topology.w
    if mode == "dense":
        if codec is not None:
            raise ValueError(
                "dense gossip ships the dense emulation by definition; "
                "bit-packed wire formats need gossip mode 'ring' or "
                "'packed'")
        mix = make_dense_mixer(w)
    elif mode == "ring":
        if mesh is None:
            raise ValueError("ring gossip needs a mesh")
        if schedule is not None and not schedule.is_banded_ring():
            raise ValueError(
                f"schedule {schedule.kind!r} has rounds that are not "
                "circulant ring bands; the ring wire format only supports "
                "weight-varying ring schedules -- use dense or packed "
                "gossip for churn/resampling schedules")
        if codec is not None:
            mix = make_ring_codec_mixer(w, mesh, codec, agent_axes,
                                        leaf_specs)
        else:
            mix = make_ring_mixer(w, mesh, agent_axes, leaf_specs)
    elif mode == "packed":
        if codec is not None:
            if mesh is None:
                raise ValueError("packed gossip needs a mesh")
            mix = make_packed_codec_mixer(w, mesh, codec, agent_axes,
                                          leaf_specs)
        else:
            if mesh is None or frac is None:
                raise ValueError(
                    "packed gossip needs a mesh and a top-k fraction")
            mix = make_packed_mixer(w, mesh, frac, agent_axes,
                                    leaf_specs)
    else:
        raise ValueError(f"unknown gossip mode {mode!r}")
    mix.wire_mode = mode
    mix.wire_frac = frac
    mix.schedule = schedule
    return mix


def gossip_wire_bytes(mode: str, n_agents: int, d_params: int,
                      frac: float = 1.0, dtype_bytes: int = 4) -> float:
    """Per-round bytes crossing agent links for one buffer (model-level).

    'packed' mirrors the actual block-packed format of
    :func:`make_packed_mixer`: each agent pads its buffer to PACK_BLOCK-sized
    windows and all-gathers ``max(round(frac*PACK_BLOCK), 1)`` (value, int32
    index) pairs *per window* -- ``nb * k_b`` pairs total, not
    ``max(frac*d, 1)``.  The distinction matters for small or badly padded
    buffers (a 10-element leaf still ships one full window's k_b pairs) and
    is what the wire-bytes tests pin against the executor's payload.

    Codec executors (bit-packed wire formats) are accounted by
    :func:`repro.core.wire_formats.codec_collective_bytes` against the same
    ring/packed link conventions; :meth:`CommRound.wire_bytes` reports the
    *measured* packed-buffer nbytes and keeps this model as the cross-check.
    """
    if mode == "dense":
        return float(n_agents) * d_params * dtype_bytes
    if mode == "ring":
        # n=2 folds both bands onto the single neighbor (one ppermute)
        shifts = 1.0 if n_agents == 2 else 2.0
        return shifts * d_params * dtype_bytes
    if mode == "packed":
        nb = -(-int(d_params) // PACK_BLOCK)          # windows after padding
        k_b = max(int(round(frac * PACK_BLOCK)), 1)   # pairs per window
        return float(n_agents) * nb * k_b * (dtype_bytes + 4)
    raise ValueError(mode)
