"""Operation and byte counts from a configuration's shapes.

* :func:`matmul_params` / :func:`flops_per_token` -- the model FLOPs a
  trained token needs: 6 x the parameters that enter a matmul (the LM head
  in, the embedding gather out), plus the attention score and value
  products, 6 * layers * heads * seq * (d_qk + d_v), with no halving for
  the causal mask.  Recomputation is not counted, so the count is the same
  for any implementation.  The shapes are the config file's own keys, which
  its ``flops`` group names, so a new architecture needs no code here.
* :func:`engine_round_bytes` -- the least bytes one PORTER round's EF
  updates must move through HBM: every buffer the two updates read or
  write, once (``core/porter.py``'s lines 11-14), from the state's leaf
  shapes and dtypes, however the kernels split the work.
"""

from __future__ import annotations

import math


def _product(c: dict, term) -> int:
    """A product of whole numbers and the config's values of named keys."""
    return math.prod(t if isinstance(t, int) else c[t] for t in term)


def _terms(c: dict, terms) -> int:
    return sum(_product(c, t) for t in terms)


def matmul_params(c: dict) -> int:
    """Parameters that enter a matmul: every layer's projections and the LM
    head; not the embedding gather, norms or biases.  The config file's
    ``flops`` group names them: ``layer_matmuls`` and ``head_matmuls`` are
    lists of products of its keys (and whole numbers), ``layers`` the key
    of the depth."""
    f = c["flops"]
    return (c[f["layers"]] * _terms(c, f["layer_matmuls"])
            + _terms(c, f["head_matmuls"]))


def attention_dims(c: dict):
    """(heads, d_qk, d_v) of the score and value products, from the keys
    that the config file's ``flops.attention`` names (d_qk and d_v each the
    sum of its keys)."""
    a = c["flops"]["attention"]
    return (c[a["heads"]], sum(c[k] for k in a["d_qk"]),
            sum(c[k] for k in a["d_v"]))


def flops_per_token(c: dict, seq: int) -> float:
    h, dqk, dv = attention_dims(c)
    layers = c[c["flops"]["layers"]]
    return 6.0 * matmul_params(c) + 6.0 * layers * h * seq * (dqk + dv)


def engine_round_bytes(leaves) -> int:
    """Least HBM bytes of one round's EF updates over a PorterState.

    ``leaves``: {buffer name: [(size, itemsize), ...]} for the state's
    x, v, q_x, q_v, m_x, m_v and g_prev trees (agent-stacked).  The track
    update reads q_v, m_v, v, c_v, W c_v, G, G_prev and writes v, q_v,
    m_v; the step update reads q_x, m_x, x, c_x, W c_x, v and writes x,
    q_x, m_x.  An increment c and its mix W c have their surrogate's dtype.
    """
    def b(name):
        return sum(size * item for size, item in leaves[name])
    track = (3 * b("q_v") + b("m_v") + b("v") + 2 * b("g_prev")
             + b("v") + b("q_v") + b("m_v"))
    step = (3 * b("q_x") + b("m_x") + b("x") + b("v")
            + b("x") + b("q_x") + b("m_x"))
    return track + step


def state_leaf_bytes(state) -> dict:
    """{buffer: [(size, itemsize), ...]} of a PorterState (arrays or
    ShapeDtypeStructs)."""
    import jax
    import numpy as np
    out = {}
    for name in ("x", "v", "q_x", "q_v", "m_x", "m_v", "g_prev"):
        out[name] = [(int(math.prod(l.shape)), np.dtype(l.dtype).itemsize)
                     for l in jax.tree_util.tree_leaves(getattr(state, name))]
    return out
