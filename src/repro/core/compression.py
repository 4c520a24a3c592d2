"""Communication compression operators (paper Definition 3).

A rho-compressor is a (possibly randomized, possibly biased) map C with

    E || C(x) - x ||_2^2  <=  (1 - rho) ||x||_2^2 ,   rho in [0, 1].

Instances implemented here:

* ``identity``      rho = 1 (no compression)
* ``random_k``      paper Example 1 -- Bernoulli(k/d) mask, *biased*, rho = k/d
* ``top_k``         paper Example 2 -- global magnitude top-k, rho = k/d
* ``block_top_k``   TPU-idiomatic top-k performed per fixed-size block
                    (still rho = k/d; see kernels/block_topk.py for the
                    Pallas version -- this module is the jnp reference)
* ``qsgd``          scaled stochastic quantizer; the unbiased QSGD operator
                    Q satisfies E||Q(x)-x||^2 <= omega ||x||^2, so the scaled
                    version Q/(1+omega) is a rho = 1/(1+omega) compressor.

``top_k`` selects without a sort: it finds the k-th largest |x| on the bit
patterns of |x| (u16 for bf16/f16, u32 for f32; for non-negative floats the
order of the patterns is the order of the values), two magnitude bits per
counting pass (8 passes for 16-bit floats, 16 for f32), keeps every coordinate
above that cut and, among those equal to it, the lowest flat indices until k
are kept (``jax.lax.top_k``'s tie rule), and writes the dense output with a
``where``.  The operator is Example 2's, global over the whole leaf; the kept
set is exactly the one a sort of |x| gives.

All compressors operate on flat vectors; :func:`compress_tree` maps a
compressor over an agent-stacked pytree, giving every (agent, leaf) pair an
independent PRNG stream.

Dense emulation vs. wire format: the functions here return *dense* arrays (the
zeros are materialized) which is what the convergence math sees.  The
bit-packed layouts that actually shrink collective bytes are registered in
:mod:`repro.core.wire_formats` -- one shared constants module (PACK_BLOCK,
``topk_bits`` for the top-k family, ``qsgd_bits`` for qsgd) consumed by the
codec gossip executors (:mod:`repro.core.gossip`), the fused pallas kernels
(:mod:`repro.kernels.wire_pack`), and the byte accounting
(:meth:`repro.core.comm_round.CommRound.wire_bytes`), so the three cannot
drift.  Select them with ``ExperimentSpec(wire="packed_bits")``.

bf16 payload note (Definition 3): the ``topk_bits`` wire format ships kept
values as bf16, so the shipped operator is C'(x) = bf16(C(x)) rather than
C(x).  Rounding each kept value multiplies it by (1 + eps) with
|eps| <= 2^-8, hence ||C'(x) - x||^2 <= (1 - rho') ||x||^2 with
rho' >= rho * (1 - 2^-8)^2 ~ rho * 0.992 -- still a valid (slightly
smaller) Definition-3 constant; gamma derived from the registry's rho is
conservative by < 1%.  ``qsgd_bits`` code words are exact (the per-window
f32 scale carries all rounding), so its rho is unchanged.

The same bound covers RESIDENT bf16 planes (``ExperimentSpec(
plane_dtype="bf16")``, SPerf-9): the EF buffers q/m live in bf16, so the
engine's effective operator is again bf16-rounded, C'(x) = bf16(C(x)) --
except the writeback is a *stochastic* rounding (kernels/sr_cast.py), so
on top of the worst-case rho' >= rho * (1 - 2^-8)^2 per-step bound the
rounding error is mean-zero and does not accumulate directionally in the
EF recursion (a round-to-nearest writeback would re-round the same drift
the same way every step and break the contraction *in expectation*; SR
preserves it).  gamma derived from the registry's rho therefore stays
conservative for bf16 planes too.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Compressor",
    "identity",
    "random_k",
    "top_k",
    "block_top_k",
    "qsgd",
    "low_rank",
    "sign",
    "make_compressor",
    "compress_tree",
    "topk_pack",
    "topk_unpack",
]


@dataclasses.dataclass(frozen=True)
class Compressor:
    """A rho-compression operator (Definition 3).

    Attributes:
      name: registry name.
      rho: contraction factor in (0, 1]; E||C(x)-x||^2 <= (1-rho)||x||^2.
      fn: (key, x) -> compressed dense x (same shape/dtype).
      deterministic: True when ``fn`` ignores the key (e.g. top-k).
      bits_per_element: estimated wire bits per *transmitted* element, used by
        the communication accounting (32 for sparse value+index schemes
        counts value bits; index bits are added by the accounting).
    """

    name: str
    rho: float
    fn: Callable[[jax.Array, jax.Array], jax.Array]
    deterministic: bool = False
    bits_per_element: int = 32

    def __call__(self, key: Optional[jax.Array], x: jax.Array) -> jax.Array:
        if key is None:
            key = jax.random.PRNGKey(0)
        return self.fn(key, x)

    def wire_bits(self, d: int) -> float:
        """Estimated bits on the wire for one compressed d-vector."""
        if self.name == "identity":
            return 32.0 * d
        if self.name == "qsgd":
            return self.bits_per_element * d
        if self.name == "sign":
            return 1.0 * d + 32.0   # one bit per coordinate + the f32 scale
        # sparse schemes: value + log2(d) index bits per kept element
        k = max(int(round(self.rho * d)), 1)
        return k * (self.bits_per_element + float(np.ceil(np.log2(max(d, 2)))))


def _identity(key, x):
    del key
    return x


def identity() -> Compressor:
    return Compressor("identity", 1.0, _identity, deterministic=True)


def random_k(frac: float) -> Compressor:
    """Paper Example 1: keep each coordinate w.p. ``frac`` (biased, no rescale)."""

    def fn(key, x):
        mask = jax.random.bernoulli(key, frac, x.shape)
        return jnp.where(mask, x, jnp.zeros_like(x))

    return Compressor(f"random_k({frac})", float(frac), fn)


# Magnitude bits the cut search sets per counting pass (see ``_topk_dense``):
# on a v5e one exchange of 2 x 172M bf16 took 51 ms at 1 bit, 33 at 2, 32 at 4.
_BITS_PER_PASS = 2


def _keep_top(v: jax.Array, key: jax.Array, cut, n_ge, k: int) -> jax.Array:
    """Zero all of the rows ``v`` but the entries whose ``key`` is above
    ``cut`` and, of those equal to it, the lowest flat indices until k are
    kept (``n_ge`` entries are at or above the cut): every tie of the rows
    before row b, and a prefix of row b's."""
    at = key == cut
    per_row = jnp.sum(at, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per_row)
    room = k - (n_ge - upto[-1])
    b = jnp.minimum(jnp.sum(upto <= room, dtype=jnp.int32), v.shape[0] - 1)
    in_b = (upto[b] - per_row[b]
            + jnp.cumsum(key[b] == cut, dtype=jnp.int32)) <= room
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    ties = at & ((row < b) | ((row == b) & in_b))
    return jnp.where((key > cut) | ties, v, jnp.zeros_like(v))


def _topk_dense(x: jax.Array, k: int) -> jax.Array:
    """Keep the k largest |x|, lowest flat index first among equals.

    The cut, the k-th largest |x|, is the largest t with at least k entries
    at or above it on the magnitude bits (``key``), so it is found from the
    top bit down: each pass counts the entries at or above the
    ``2**_BITS_PER_PASS - 1`` candidates that extend t by one digit and
    keeps the largest candidate that still has k of them.  The last pass
    also writes the output.  Every pass reads the leaf from the loop's
    carry, so the selection and the values kept come from one copy of x
    even where XLA would otherwise recompute x's producer per consumer.
    """
    if k >= x.size:
        return x
    v = x.reshape(-1, x.shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    width = x.dtype.itemsize * 8
    uint = {16: jnp.uint16, 32: jnp.uint32}[width]
    r = _BITS_PER_PASS
    passes = -(-(width - 1) // r)
    assert passes * r <= width, (width, r)

    def one_pass(i, carry):
        base, n, v = carry
        key = (jax.lax.bitcast_convert_type(v, uint)
               & jnp.asarray((1 << (width - 1)) - 1, uint))
        t = base
        shift = ((passes - 1 - i) * r).astype(uint)
        for digit in range(1, 2 ** r):
            cand = base | (jnp.asarray(digit, uint) << shift)
            count = jnp.sum(key >= cand, dtype=jnp.int32)
            t, n = (jnp.where(count >= k, cand, t),
                    jnp.where(count >= k, count, n))
        v = jax.lax.cond(i == passes - 1,
                         functools.partial(_keep_top, k=k),
                         lambda v, *_: v, v, key, t, n)
        return t, n, v

    _, _, out = jax.lax.fori_loop(
        0, passes, one_pass,
        (jnp.zeros((), uint), jnp.asarray(x.size, jnp.int32), v))
    return out.reshape(x.shape)


def top_k(frac: float) -> Compressor:
    """Paper Example 2: keep the k = frac*d largest-magnitude coordinates.

    Global over the whole leaf, k = max(round(frac * d), 1).  The cut is the
    k-th largest |x|, found on the bits of |x| (for non-negative floats the
    order of the values is the order of their patterns) by counting passes
    that set two magnitude bits each: 8 for bf16 and f16, 16 for f32.  Among
    entries equal to the cut the lowest flat indices are kept, the tie rule
    of ``jax.lax.top_k``, so the kept set is the one a sort of |x| gives; no
    sort and no scatter.
    """

    def fn(key, x):
        del key
        k = max(int(round(frac * x.size)), 1)
        return _topk_dense(x, k)

    return Compressor(f"top_k({frac})", float(frac), fn, deterministic=True)


def block_top_k(frac: float, block: int = 2048) -> Compressor:
    """Per-block top-k: the TPU-idiomatic variant (see kernels/block_topk.py).

    Selecting k_b = frac*block elements independently inside each ``block``-sized
    window still satisfies Definition 3 with rho = frac: the error in each block
    is at most (1-frac) of that block's energy, and energies add.
    """

    def fn(key, x):
        del key
        flat = x.reshape(-1)
        d = flat.shape[0]
        pad = (-d) % block
        padded = jnp.pad(flat, (0, pad))
        blocks = padded.reshape(-1, block)
        k_b = max(int(round(frac * block)), 1)
        _, idx = jax.lax.top_k(jnp.abs(blocks), k_b)
        vals = jnp.take_along_axis(blocks, idx, axis=1)
        out = jnp.zeros_like(blocks)
        out = jax.vmap(lambda o, i, v: o.at[i].set(v))(out, idx, vals)
        return out.reshape(-1)[:d].reshape(x.shape)

    return Compressor(f"block_top_k({frac},{block})", float(frac), fn,
                      deterministic=True)


def low_rank(rank: int = 2, power_iters: int = 1) -> Compressor:
    """PowerSGD-style rank-r compressor [Vogels et al. 2019], adapted to the
    Definition-3 contract.

    The input vector is reshaped to a near-square matrix M; ``power_iters``
    subspace iterations with a fixed (key-seeded) Gaussian sketch give an
    orthonormal Q whose projection P = (M Q) Q^T is the best-effort rank-r
    approximation.  Projections are contractions (||P - M||^2 <= ||M||^2 with
    strict inequality unless M is rank-deficient), so Definition 3 holds with
    a data-dependent rho; we report the conservative floor
    rho >= rank / min_dim for random matrices (validated empirically in
    tests/test_compression.py).  Wire format: the (m, r) + (n, r) factors --
    r*(m+n) floats instead of m*n.
    """

    def fn(key, x):
        flat = x.reshape(-1).astype(jnp.float32)
        d = flat.shape[0]
        m = int(np.ceil(np.sqrt(d)))
        n = int(np.ceil(d / m))
        pad = m * n - d
        mat = jnp.pad(flat, (0, pad)).reshape(m, n)
        r = min(rank, m, n)
        q = jax.random.normal(key, (n, r))
        for _ in range(power_iters):
            p_ = mat @ q                       # (m, r)
            p_, _ = jnp.linalg.qr(p_)
            q = mat.T @ p_                     # (n, r)
        q_orth, _ = jnp.linalg.qr(q)
        approx = (mat @ q_orth) @ q_orth.T
        return approx.reshape(-1)[:d].reshape(x.shape).astype(x.dtype)

    return Compressor(f"low_rank({rank})", 0.0, fn)  # rho data-dependent


def sign() -> Compressor:
    """l1-scaled sign compressor [KRSJ19]: C(x) = (||x||_1 / d) sign(x).

    Deterministic 1-bit-per-coordinate scheme (the shipped payload is the
    sign bitmap plus one f32 scale; see :meth:`Compressor.wire_bits`).
    Definition 3 holds with the data-dependent
    rho(x) = ||x||_1^2 / (d ||x||_2^2), which Cauchy-Schwarz bounds below
    by 1/d; like ``low_rank`` the registry reports the conservative 0.0
    and the contract suite checks the exact per-d floor.
    """

    def fn(key, x):
        del key
        flat = x.reshape(-1).astype(jnp.float32)
        scale = jnp.mean(jnp.abs(flat))
        out = scale * jnp.sign(flat)
        return out.reshape(x.shape).astype(x.dtype)

    return Compressor("sign", 0.0, fn, deterministic=True,
                      bits_per_element=1)


def qsgd(levels: int = 16) -> Compressor:
    """Scaled stochastic quantizer.

    QSGD with s levels is unbiased with relative variance
    omega <= min(d/s^2, sqrt(d)/s).  Scaling the output by 1/(1+omega) turns it
    into a rho = 1/(1+omega) contraction (standard trick, cf. [RSF21]).
    omega depends on d, so rho here is a conservative static bound computed for
    d up to ~1e9 via the sqrt(d)/s branch at construction time is impossible;
    instead we compute the scale per-call from the actual d.
    """

    def fn(key, x):
        flat = x.reshape(-1)
        d = flat.shape[0]
        norm = jnp.linalg.norm(flat) + 1e-30
        y = jnp.abs(flat) / norm * levels
        lo = jnp.floor(y)
        prob = y - lo
        rnd = jax.random.uniform(key, flat.shape)
        q = (lo + (rnd < prob)) / levels
        omega = min(np.sqrt(d) / levels, d / levels**2)
        out = jnp.sign(flat) * q * norm / (1.0 + omega)
        return out.reshape(x.shape).astype(x.dtype)

    # rho reported for "typical" d ~ 1e6; exact value enforced in tests per-d.
    omega_typ = np.sqrt(1e6) / levels
    return Compressor(f"qsgd({levels})", float(1.0 / (1.0 + omega_typ)), fn,
                      bits_per_element=int(np.ceil(np.log2(levels + 1))) + 1)


_REGISTRY = {
    "identity": identity,
    "random_k": random_k,
    "top_k": top_k,
    "block_top_k": block_top_k,
    "qsgd": qsgd,
    "low_rank": low_rank,
    "sign": sign,
}


def make_compressor(name: str, **kwargs) -> Compressor:
    if name not in _REGISTRY:
        raise ValueError(f"unknown compressor {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def compress_tree(comp: Compressor, key: jax.Array, tree):
    """Apply ``comp`` leaf-wise to a pytree with independent PRNG streams.

    Leaves may carry a leading agent axis; compression is applied to the whole
    leaf buffer per agent row (vmapped) so every agent compresses its own
    vector independently, as in the paper.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))

    def one(key, leaf):
        if leaf.ndim >= 2:  # (n_agents, ...) -> compress per agent row
            n = leaf.shape[0]
            ks = jax.random.split(key, n)
            return jax.vmap(lambda kk, row: comp(kk, row))(ks, leaf)
        return comp(key, leaf)

    return treedef.unflatten([one(k, l) for k, l in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# Packed top-k wire format (used by gossip 'packed_topk' mode).
# ---------------------------------------------------------------------------

def topk_pack(x: jax.Array, k: int):
    """Pack a vector into (values, int32 indices) of its top-k magnitudes."""
    flat = x.reshape(-1)
    vals_abs, idx = jax.lax.top_k(jnp.abs(flat), k)
    del vals_abs
    return flat[idx], idx.astype(jnp.int32)


def topk_unpack(values: jax.Array, indices: jax.Array, d: int) -> jax.Array:
    """Scatter packed (values, indices) back into a dense d-vector."""
    return jnp.zeros((d,), values.dtype).at[indices].set(values)
