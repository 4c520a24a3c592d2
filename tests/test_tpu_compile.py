"""Compile-only checks: the train path's Pallas kernels at real widths, for
a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, and asserts that the compiled program holds the Mosaic
kernel (``tpu_custom_call``).  This is what interpret-mode parity cannot
show -- block shapes, scalar memory spaces and in-kernel ops the TPU
lowering refuses.  Widths: the plane of one minicpm3-4b MLP weight
(2560 x 6400) and its 2048-element wire windows at rho = 0.05.

The topology is described inside a module fixture, never at import: only
the pytest worker that runs this file loads the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import wire_formats as WF
from repro.kernels import ops

MLP = (2560, 6400)          # one minicpm3-4b MLP weight
ODD = (3, 1000, 7)          # not a tile multiple: a partial last block
FRAC = 0.05
LEVELS = 7


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_count(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


EF = {
    "ef_track": (7, lambda *a: ops.ef_track(*a, 0.3, interpret=False,
                                             out_dtype=jnp.float32)),
    "ef_step": (6, lambda *a: ops.ef_step(*a, 0.3, 0.03, interpret=False)),
    "ef_gossip": (5, lambda *a: ops.ef_gossip(*a, 0.3, 0.5,
                                              interpret=False)),
}


@pytest.mark.parametrize("shape", [MLP, ODD])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", sorted(EF))
def test_ef_kernels_compile_for_v5e(chip, name, dtype, shape):
    n_in, fn = EF[name]
    assert _kernel_count(fn, *[_sds(chip, shape, dtype)] * n_in) == 1


@pytest.mark.parametrize("shape", [MLP, ODD])
def test_sr_cast_compiles_for_v5e(chip, shape):
    assert _kernel_count(lambda x, k: ops.sr_cast(x, k, interpret=False),
                         _sds(chip, shape),
                         _sds(chip, (2,), jnp.uint32)) == 1


@pytest.mark.parametrize("windows", [MLP[0] * MLP[1] // WF.PACK_BLOCK, 13])
def test_topk_codec_compiles_for_v5e(chip, windows):
    k = WF.topk_keep(FRAC)

    def roundtrip(rows):
        vals, idx = ops.wire_topk_pack(rows, k, interpret=False)
        assert vals.dtype == WF.TOPK_VALUE_DTYPE
        return ops.wire_topk_unpack(vals, idx, interpret=False)

    assert _kernel_count(roundtrip,
                         _sds(chip, (windows, WF.PACK_BLOCK))) == 2


@pytest.mark.parametrize("windows", [MLP[0] * MLP[1] // WF.PACK_BLOCK, 13])
def test_qsgd_codec_compiles_for_v5e(chip, windows):
    def roundtrip(rows, key):
        word, scale = ops.wire_qsgd_pack(rows, key, LEVELS, interpret=False)
        assert word.dtype == jnp.uint32
        return ops.wire_qsgd_unpack(word, scale, LEVELS, interpret=False)

    assert _kernel_count(roundtrip, _sds(chip, (windows, WF.PACK_BLOCK)),
                         _sds(chip, (2,), jnp.uint32)) == 2
