"""Plain reference of the chatglm3-6b decoder: next-token loss from weights.

Straightforward jax.numpy, one layer after another, no caches, kernels or
chunking.  Imports nothing of the program.  ``dot(spec, a, b)`` is the
einsum at the precision the caller chooses (f32 at ``highest`` for the
reference, lower for the control).

Layer equations (grouped-query attention, ChatGLM3):

    q = h Wq + bq ;  k = h Wk + bk ;  v = h Wv + bv       (32 q heads, 2 kv
                                                          heads, 128 each)
    q, k = rope(q, k) on the first half (64) of each head's channels
    a   = softmax_causal(q k / sqrt(128)) v Wo   (16 q heads per kv head)
    h' = x + a ;  x' = h' + (silu(rms(h') Wg) * rms(h') Win) Wout
    logits = rms(x_L) Whead   (untied head)

Departures from the published model, shared with the program: rotary
pairs are the two halves of the rotated channels (rotate-half) rather than
adjacent channels, and the rms epsilon is 1e-6.
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def rope(x, pos, rot, theta=10000.0):
    """Rotate-half rotary embedding of the first ``rot`` channels of
    x (B, S, H, d)."""
    freqs = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = (pos[:, None] * freqs[None, :])[:, None, :]     # (S, 1, rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def layer(p, x, c, dot):
    b, s, d = x.shape
    h_n, kv, hd = (c["num_attention_heads"], c["multi_query_group_num"],
                   c["kv_channels"])
    rot = int(hd * c["rotary_fraction"]) // 2 * 2
    pos = jnp.arange(s, dtype=jnp.float32)
    a = p["attn"]

    def proj(w, heads):
        y = dot("bsd,de->bse", h, a[w]["w"]) + a[w]["b"]
        return y.reshape(b, s, heads, hd)

    h = rms(x, p["ln1"]["scale"])
    q = rope(proj("wq", h_n), pos, rot).reshape(b, s, kv, h_n // kv, hd)
    k = rope(proj("wk", kv), pos, rot)
    v = proj("wv", kv)
    scores = dot("bskgd,btkd->bkgst", q, k) / math.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = dot("bkgst,btkd->bskgd", probs, v).reshape(b, s, h_n * hd)
    x = x + dot("bse,ed->bsd", o, a["wo"]["w"])
    f = p["ffn"]
    h = rms(x, p["ln2"]["scale"])
    g = jax.nn.silu(dot("bsd,df->bsf", h, f["w_gate"]["w"]))
    u = dot("bsd,df->bsf", h, f["w_in"]["w"])
    return x + dot("bsf,fd->bsd", g * u, f["w_out"]["w"])


def loss(params, tokens, c, dot):
    """Mean next-token cross-entropy of tokens (B, S) under params."""
    x = params["embed"]["table"][tokens]
    for i in range(c["num_layers"]):
        x = layer(jax.tree_util.tree_map(lambda l: l[i], params["layers"]),
                  x, c, dot)
    x = rms(x, params["final_norm"]["scale"])
    logits = dot("bsd,dv->bsv", x, params["head"]["w"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
