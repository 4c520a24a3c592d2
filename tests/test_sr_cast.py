"""Hypothesis sweep on the stochastic-rounding f32 -> bf16 cast.

The mixed-precision engine's correctness argument leans on three facts
about ``sr_cast`` (see kernels/sr_cast.py):

* **bracketing** -- the output is always one of the two bf16 neighbours of
  the input (never a different binade, never a sign flip), so a single
  writeback moves a plane by at most one ulp;
* **exactness** -- bf16-representable values never move, for any key (the
  EF recursion's fixed points stay fixed);
* **unbiasedness** -- E[sr(x)] = x, so the bf16 EF drift on ``q``/``m``
  is mean-zero and the compression contraction survives in expectation.

Plus the system-level pin: the pallas kernel (interpret mode) and the jnp
reference consume identical bits drawn outside the kernel, so they are
BIT-identical for the same key on every odd, non-tile-aligned shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import sr_cast as SRK

# odd shapes: scalar, tiny, non-lane-aligned, 3-D, crosses a tile boundary
ODD_SHAPES = [(), (1,), (123,), (7, 11, 3), (9001,)]


def _uniform(key, shape, scale):
    return scale * jax.random.uniform(key, shape, jnp.float32,
                                      minval=-1.0, maxval=1.0)


def _brackets(x):
    """The two admissible bf16 outputs, in bit space: truncate-down and
    (when the low mantissa bits are nonzero) the next representable."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    lo = (b >> 16).astype(jnp.uint16)
    hi = lo + (b & jnp.uint32(0xFFFF) != 0).astype(jnp.uint16)
    return lo, hi


@given(st.integers(0, 2**16), st.sampled_from([1e-3, 1.0, 1e3]))
@settings(max_examples=16, deadline=None)
def test_bracketing(seed, scale):
    x = _uniform(jax.random.PRNGKey(seed), (257,), scale)
    lo, hi = _brackets(x)
    y = ops.sr_cast_ref(x, jax.random.PRNGKey(seed + 1))
    yb = jax.lax.bitcast_convert_type(y, jnp.uint16)
    assert bool(jnp.all((yb == lo) | (yb == hi)))


@given(st.integers(0, 2**16))
@settings(max_examples=16, deadline=None)
def test_exact_values_never_move(seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = _uniform(k1, (300,), 2.0).astype(jnp.bfloat16).astype(jnp.float32)
    y = ops.sr_cast_ref(x, k2)
    np.testing.assert_array_equal(np.asarray(y, jnp.float32),
                                  np.asarray(x))


@given(st.integers(0, 2**16), st.sampled_from([1e-2, 1.0]))
@settings(max_examples=8, deadline=None)
def test_unbiased_mean(seed, scale):
    """Mean over many independent roundings converges to x: the residual
    shrinks as gap/sqrt(K), tested at ~7 sigma so flakes are negligible."""
    x = _uniform(jax.random.PRNGKey(seed), (64,), scale)
    keys = jax.random.split(jax.random.PRNGKey(seed + 7), 512)
    ys = jax.vmap(lambda k: ops.sr_cast_ref(x, k).astype(jnp.float32))(keys)
    lo, hi = _brackets(x)
    # bit-space neighbours order by magnitude, so the value gap needs abs
    # (for x < 0 the +1 neighbour is the more negative one)
    gap = jnp.abs(
        jax.lax.bitcast_convert_type(hi, jnp.bfloat16).astype(jnp.float32)
        - jax.lax.bitcast_convert_type(lo, jnp.bfloat16).astype(jnp.float32))
    err = jnp.abs(jnp.mean(ys, axis=0) - x)
    # sigma(mean) <= gap / (2 sqrt(512)) ~= 0.0221 * gap
    assert bool(jnp.all(err <= 0.16 * gap + 1e-12))


@given(st.integers(0, 2**16))
@settings(max_examples=6, deadline=None)
def test_pallas_interpret_bit_parity(seed):
    """kernel (interpret) == jnp reference, bit for bit, on odd shapes --
    both draw the same bits outside the kernel from the same key."""
    key = jax.random.PRNGKey(seed)
    for shape in ODD_SHAPES:
        kx, kr = jax.random.split(jax.random.fold_in(key, len(shape)))
        x = _uniform(kx, shape, 3.0)
        a = ops.sr_cast(x, kr, interpret=True)
        b = ops.sr_cast_ref(x, kr)
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16)),
            np.asarray(jax.lax.bitcast_convert_type(b, jnp.uint16)),
            err_msg=f"shape {shape}")


@given(st.integers(0, 2**16))
@settings(max_examples=8, deadline=None)
def test_leaf_cast_properties(seed):
    """sr_cast_leaf (the sharding-preserving writeback path) obeys the same
    bracketing/exactness contract as the padded-plane pair."""
    key = jax.random.PRNGKey(seed)
    for shape in [(), (5,), (4, 33)]:
        kx, kr = jax.random.split(jax.random.fold_in(key, len(shape)))
        x = _uniform(kx, shape, 2.0)
        y = ops.sr_cast_leaf(x, kr)
        assert y.dtype == jnp.bfloat16 and y.shape == shape
        lo, hi = _brackets(x)
        yb = jax.lax.bitcast_convert_type(y, jnp.uint16)
        assert bool(jnp.all((yb == lo) | (yb == hi)))
        xe = x.astype(jnp.bfloat16).astype(jnp.float32)
        ye = ops.sr_cast_leaf(xe, kr)
        np.testing.assert_array_equal(np.asarray(ye, jnp.float32),
                                      np.asarray(xe))


def test_kernel_level_parity_padded_plane():
    """The raw (tiles, 8 * 1024) kernel matches its reference on shared
    bits."""
    key = jax.random.PRNGKey(3)
    x = _uniform(key, (3, 8 * 1024), 1.0)
    bits = jax.random.bits(jax.random.fold_in(key, 1), x.shape, jnp.uint32)
    a = SRK.sr_cast(x, bits, interpret=True)
    b = SRK.sr_cast_ref(x, bits)
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16)),
        np.asarray(jax.lax.bitcast_convert_type(b, jnp.uint16)))
