"""CPU tests of the FLOP and byte counts the per-layer readers divide by."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as C
from chipbench import counts as K

HERE = Path(__file__).resolve().parent
CONFIGS = sorted(p.stem for p in (HERE / "configs").glob("*.json"))


def _config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_matmul_params_are_the_model_minus_the_gather(name):
    """6 x matmul params counts every projection and the LM head: the
    model's parameters less the embedding gather (when untied) and the
    norm scales and biases, which enter no matmul."""
    from repro.models import build_model
    config = _config(name)
    cfg = C.model_config(config)
    shapes = jax.eval_shape(lambda k: build_model(cfg).init(k)[0],
                            jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = sum(s.size for _, s in flat)
    vectors = sum(s.size for p, s in flat
                  if C.leaf_name(p).rsplit("/", 1)[-1] in ("scale", "b"))
    gather = 0 if cfg.tie_embeddings else cfg.vocab * cfg.d_model
    assert K.matmul_params(config) == total - vectors - gather


def test_minicpm3_flops_per_token_by_hand():
    c = _config("minicpm3-4b")
    layer = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 256 * 40 * 64
             + 256 * 40 * 64 + 40 * 64 * 2560 + 3 * 2560 * 6400)
    want = 6 * (2 * layer + 2560 * 18362) + 6 * 2 * 40 * 1024 * (96 + 64)
    assert K.flops_per_token(c, 1024) == want
    assert 1.10e9 < want < 1.12e9


def test_engine_round_bytes_by_hand():
    """Two agents of 17 parameters, f32 x and bf16 planes: the track update
    moves 7 reads + 3 writes of 2 B, the step update reads q, m, c, W c, v
    (2 B) and x (4 B) and writes q, m (2 B) and x (4 B): 42 B a parameter."""
    from repro.core.porter import porter_init
    params = {"a": jnp.zeros((3, 4)), "b": jnp.zeros((5,))}
    state = porter_init(params, 2, plane_dtype=jnp.bfloat16)
    per_param = (7 * 2 + 3 * 2) + (5 * 2 + 4 + 2 * 2 + 4)
    assert per_param == 42
    assert K.engine_round_bytes(K.state_leaf_bytes(state)) == 2 * 17 * 42
