"""Pallas TPU kernel: stochastic-rounding f32 -> bf16 cast.

The mixed-precision engine keeps its EF state planes (``q``, ``m``, ``v``)
in bf16 but accumulates every update in f32 inside the fused kernels
(:mod:`repro.kernels.ef_update`).  A round-to-nearest writeback would bias
the EF recursion: the same tiny increment rounds the same way every step,
so drift accumulates in a fixed direction and the compressed-difference
contraction (Definition 3) no longer holds in expectation.  Stochastic
rounding makes the writeback unbiased, ``E[sr(x)] = x`` within a binade:

    bf16_bits(x) = high16( bits(x) + (r & 0xFFFF) )      r ~ U[0, 2^32)

i.e. add a uniform random value strictly below the truncated mantissa cut,
then truncate -- values exactly representable in bf16 (low 16 bits zero)
never move, and anything in between rounds up with probability equal to
its fractional position between the two neighbouring bf16 values.

The random bits are drawn *outside* the kernel (``jax.random.bits`` from a
threaded key) and passed as an operand, exactly like the QSGD pack kernel's
dither noise: the pallas kernel and the pure-jnp reference then consume
identical bits, so ``sr_cast`` (interpret or compiled) and
:func:`sr_cast_ref` are bit-identical for the same key -- which is what the
parity tests pin.

Non-finite caveat: the bit-space add walks NaN payloads and can wrap a
negative NaN; the EF planes are finite by construction (clipped gradients,
bounded mixing), so the kernel does not special-case them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ef_update import grid_call

__all__ = ["sr_cast", "sr_cast_ref"]


def _sr_body(vals, bits):
    """Shared f32->bf16 stochastic-rounding arithmetic (jnp ops only).

    Masks/shift amounts are built inside the body (not module-level
    constants): pallas_call rejects captured traced constants.
    """
    b = jax.lax.bitcast_convert_type(vals.astype(jnp.float32), jnp.uint32)
    r = bits & jnp.uint32(0xFFFF)
    hi = ((b + r) >> jnp.uint32(16)).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(hi, jnp.bfloat16)


def _sr_kernel(x_ref, r_ref, o_ref):
    o_ref[...] = _sr_body(x_ref[...], r_ref[...])


def sr_cast(x, bits, interpret: bool = False):
    """Stochastically round an f32 array (any shape) to bf16.

    ``bits``: uint32 array of the same shape (only the low 16 bits of each
    word are used).
    """
    if x.shape != bits.shape:
        raise ValueError(f"sr_cast shape mismatch: {x.shape} vs {bits.shape}")
    return grid_call(_sr_kernel, (x, bits), (), 1, jnp.bfloat16,
                     interpret)[0]


def sr_cast_ref(x, bits):
    """jnp reference: bit-identical to :func:`sr_cast` on the same bits."""
    if x.shape != bits.shape:
        raise ValueError(f"sr_cast shape mismatch: {x.shape} vs {bits.shape}")
    return _sr_body(x, bits)
