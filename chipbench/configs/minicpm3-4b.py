"""Plain reference of the minicpm3-4b decoder: next-token loss from weights.

Straightforward jax.numpy, one layer after another, no caches, kernels or
chunking.  Imports nothing of the program.  ``dot(spec, a, b)`` is the
einsum at the precision the caller chooses (f32 at ``highest`` for the
reference, lower for the control).

Layer equations (multi-head latent attention, MiniCPM3 / DeepSeek-V2):

    cq  = rms(h Wdq) ;  [q_nope | q_rope] = cq Wuq         (per head 64 | 32)
    [ckv | kr] = h Wdkv ;  ckv = rms(ckv)                  (256 | 32)
    k_nope = ckv Wuk ;  v = ckv Wuv                        (per head 64, 64)
    q_rope, kr = rope(q_rope), rope(kr)  (one rope key shared by all heads)
    a   = softmax_causal((q_nope k_nope + q_rope kr) / sqrt(96)) v Wo
    h' = x + a ;  x' = h' + (silu(rms(h') Wg) * rms(h') Win) Wout
    logits = rms(x_L) E^T   (tied embeddings)

Departures from the published model, shared with the program: no muP
scalings (scale_emb, scale_depth), rms epsilon 1e-6, rope without the
longrope scaling.
"""

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def rope(x, pos, theta):
    """Rotate-half rotary embedding over the last axis of x (..., S, [H,] d)."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * freqs[None, :]                # (S, d/2)
    if x.ndim == 4:                                    # (B, S, H, d)
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, c, dot):
    b, s, d = x.shape
    h_n = c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    dc = c["kv_lora_rank"]
    pos = jnp.arange(s, dtype=jnp.float32)
    a = p["attn"]
    h = rms(x, p["ln1"]["scale"])
    cq = rms(dot("bsd,de->bse", h, a["wdq"]["w"]), a["q_norm"]["scale"])
    q = dot("bsr,re->bse", cq, a["wuq"]["w"]).reshape(b, s, h_n, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, c["rope_theta"])
    dkv = dot("bsd,de->bse", h, a["wdkv"]["w"])
    ckv = rms(dkv[..., :dc], a["kv_norm"]["scale"])
    kr = rope(dkv[..., dc:], pos, c["rope_theta"])
    k_nope = dot("bsc,ce->bse", ckv, a["wuk"]["w"]).reshape(b, s, h_n, dn)
    v = dot("bsc,ce->bse", ckv, a["wuv"]["w"]).reshape(b, s, h_n, dv)
    scores = (dot("bshd,bthd->bhst", q_nope, k_nope)
              + dot("bshd,btd->bhst", q_rope, kr)) / math.sqrt(dn + dr)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = dot("bhst,bthd->bshd", probs, v).reshape(b, s, h_n * dv)
    x = x + dot("bse,ed->bsd", o, a["wo"]["w"])
    f = p["ffn"]
    h = rms(x, p["ln2"]["scale"])
    g = jax.nn.silu(dot("bsd,df->bsf", h, f["w_gate"]["w"]))
    u = dot("bsd,df->bsf", h, f["w_in"]["w"])
    return x + dot("bsf,fd->bsd", g * u, f["w_out"]["w"])


def loss(params, tokens, c, dot):
    """Mean next-token cross-entropy of tokens (B, S) under params."""
    x = params["embed"]["table"][tokens]
    for i in range(c["num_hidden_layers"]):
        x = layer(jax.tree_util.tree_map(lambda l: l[i], params["layers"]),
                  x, c, dot)
    x = rms(x, params["final_norm"]["scale"])
    logits = dot("bsd,vd->bsv", x, params["embed"]["table"])
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
