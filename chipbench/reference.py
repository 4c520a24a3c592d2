"""Plain reference of the first rounds of PORTER, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  From the seed it makes the
same weights and tokens the benchmark hands the program (``cell.make_params``
and ``cell.make_source`` are the benchmark's own generators), and follows
Algorithm 1 of the paper for the rounds of the first chunk, one agent and
one leaf at a time, in float32 with matmuls at ``highest``:

    G   = Clip_tau(grad f(x; batch))                       (GC, Option II)
        = mean_z Clip_tau(grad f(x; z)) + N(0, sigma^2)    (DP, Option I)
    c_v = topk(v - q_v);  q_v += c_v;  m_v += W c_v
    v   = v + gamma (m_v - q_v) + G - G_prev
    c_x = topk(x - q_x);  q_x += c_x;  m_x += W c_x
    x   = x + gamma (m_x - q_x) - eta v

with the EF buffers (v, q, m, G_prev) stored in the traffic's plane dtype:
in bfloat16, increments narrowed by rounding to nearest and the
accumulating writebacks by stochastic rounding with the reference's own
random bits; in float32, exactly.  x is kept in float32.  W carries
Metropolis weights over the traffic's graph (``cell.adjacency``).  The
round stream follows the runtime's documented key contract:
``kb, ks = split(fold_in(key, t))``, the batch from ``kb``, and
``_, k_noise, k_cv, k_cx = split(ks, 4)`` with one noise key per agent
from ``k_noise`` and one per leaf from that.

``precision="fp8"`` is the control: the same reference with every matmul's
operands rounded to float8 (e4m3), the precision below the bfloat16 that the
configuration computes in.  ``fault=`` plants one of the faults a training
cell can have, for the upper readings of the limits.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import cell as C

HERE = Path(__file__).resolve().parent
# the EF state trees whose per-leaf norms are compared, after the first chunk
TREES = ("grad", "v", "m_v", "dx")
FAULTS = ("half_batch", "no_exchange")
# the packed wire's selection window (repro.core.wire_formats.PACK_BLOCK)
WINDOW = 2048
# the EF planes' storage: bf16 with stochastic-rounding writebacks, or f32
PLANES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def model_module(arch: str):
    """The plain forward pass kept beside the configuration's file."""
    path = HERE / "configs" / f"{arch}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{arch.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_dot(precision: str):
    if precision == "f32":
        def dot(spec, a, b):
            return jnp.einsum(spec, a, b,
                              precision=jax.lax.Precision.HIGHEST)
    elif precision == "fp8":
        def f8(x):
            # operands rounded to float8 going forward; the cotangents stay
            # float32 (the rounding passes them straight through)
            r = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return x + jax.lax.stop_gradient(r - x)

        def dot(spec, a, b):
            return jnp.einsum(spec, f8(a), f8(b),
                              precision=jax.lax.Precision.HIGHEST)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return dot


def store(x, key, plane):
    """A writeback to a plane of dtype ``plane``: stochastic rounding to
    bf16, or f32 as it is."""
    if plane == jnp.float32:
        return x.astype(jnp.float32)
    return sr_bf16(x, key)


def sr_bf16(x, key):
    """Stochastic rounding of f32 to bf16: add 16 random low bits, truncate."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    r = jax.random.bits(key, x.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    hi = ((bits + r) >> jnp.uint32(16)).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(hi, jnp.bfloat16)


def topk(x, frac):
    """Keep the k = max(round(frac * size), 1) largest |x|, zero the rest;
    among equal |x| at the cut, the lowest indices are kept.

    The k-th largest |x| is found bit by bit on its float32 pattern (the
    order of non-negative floats is the order of their bits): 31 counting
    passes instead of a sort.
    """
    flat = x.reshape(-1)
    k = max(int(round(frac * flat.size)), 1)
    bits = jax.lax.bitcast_convert_type(jnp.abs(flat.astype(jnp.float32)),
                                        jnp.uint32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << jnp.uint32(30 - i))
        return jnp.where(jnp.sum(bits >= cand) >= k, cand, t)

    cut = jax.lax.fori_loop(0, 31, bit, jnp.uint32(0))
    above = bits > cut
    at = bits == cut
    room = k - jnp.sum(above)
    keep = above | (at & (jnp.cumsum(at) <= room))
    return jnp.where(keep, flat, jnp.zeros_like(flat)).reshape(x.shape)


def metropolis_w(adj: np.ndarray) -> np.ndarray:
    """Metropolis weights of an undirected graph (Definition 1)."""
    n = adj.shape[0]
    deg = adj.sum(1)
    w = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            if adj[i, j]:
                w[i, j] = 1.0 / (1 + max(deg[i], deg[j]))
        w[i, i] = 1.0 - w[i].sum()
    return w


def mixing_gamma(w: np.ndarray, frac: float) -> float:
    """gamma = (1 - alpha) rho / 2 with alpha = ||W - J||_2 (the paper's
    stable consensus step)."""
    n = w.shape[0]
    alpha = float(np.linalg.norm(w - np.full((n, n), 1.0 / n), 2))
    return 0.5 * (1.0 - alpha) * frac


def _clip(g, tau):
    """Smooth clipping by the global norm: G tau / (tau + ||G||)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                        for l in jax.tree_util.tree_leaves(g)))
    return jax.tree_util.tree_map(lambda l: l * (tau / (tau + norm)), g)


def gradient_oracle(config: dict, precision: str, rows: str, tau: float,
                    sigma: float, dp: bool):
    """Jitted ``(x, tokens, key) -> (loss, G)`` of one agent, G in f32,
    shared by every reference of the same cell, precision and fault."""
    return _oracle(json.dumps(config, sort_keys=True), precision, rows, tau,
                   sigma, dp)


@functools.lru_cache(maxsize=None)
def _oracle(config_json: str, precision: str, rows: str, tau: float,
            sigma: float, dp: bool):
    config = json.loads(config_json)
    mod = model_module(config["arch"])
    dot = make_dot(precision)
    vg = jax.value_and_grad(lambda p, toks: mod.loss(p, toks, config, dot))

    def oracle(x, tokens, key):
        if rows == "half":
            tokens = tokens[: max(tokens.shape[0] // 2, 1)]
        if dp:
            def one(acc, z):
                l, g = vg(x, z[None])
                return jax.tree_util.tree_map(jnp.add, acc, _clip(g, tau)), l
            zeros = jax.tree_util.tree_map(jnp.zeros_like, x)
            g, losses = jax.lax.scan(one, zeros, tokens)
            g = jax.tree_util.tree_map(lambda a: a / tokens.shape[0], g)
            leaves, treedef = jax.tree_util.tree_flatten(g)
            keys = jax.random.split(key, len(leaves))
            return jnp.mean(losses), treedef.unflatten(
                [l + sigma * jax.random.normal(k, l.shape, l.dtype)
                 for k, l in zip(keys, leaves)])
        l, g = vg(x, tokens)
        return l, _clip(g, tau)

    return jax.jit(oracle)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def compress_leaf(frac, window, plane, y, q):
    """c = C(y - q) of one agent's leaf, the increment in the plane's dtype:
    top-k over the whole leaf (``window`` 0), or in each ``window``-sized
    slice of the flattened leaf, zero-padded at its end (the packed wire)."""
    delta = (y.astype(jnp.float32) - q.astype(jnp.float32)).astype(plane)
    if not window:
        return topk(delta, frac)
    flat = delta.reshape(-1)
    pad = -flat.size % window
    rows = jnp.pad(flat, (0, pad)).reshape(-1, window)
    kept = jax.vmap(lambda r: topk(r, frac))(rows).reshape(-1)
    return kept[: flat.size].reshape(delta.shape)


@functools.partial(jax.jit, static_argnums=(0, 1))
def mix_leaf(weights, plane, *cs):
    """sum_j w_ij c_j of the increments an agent receives, in the plane's
    dtype."""
    return sum(w * c.astype(jnp.float32)
               for w, c in zip(weights, cs)).astype(plane)


@functools.partial(jax.jit, static_argnums=(0, 1))
def track_update(gamma, plane, v, q, m, c, wc, g, gp, key):
    """Lines 11-12 for one agent's leaf: q += c; m += W c;
    v' = v + gamma (m - q) + G - G_prev; writebacks to the plane."""
    f32 = jnp.float32
    q2 = q.astype(f32) + c.astype(f32)
    m2 = m.astype(f32) + wc.astype(f32)
    v2 = v.astype(f32) + gamma * (m2 - q2) + g.astype(f32) - gp.astype(f32)
    kv, kq, km = jax.random.split(key, 3)
    return store(v2, kv, plane), store(q2, kq, plane), store(m2, km, plane)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def step_update(gamma, eta, plane, x, q, m, c, wc, v, key):
    """Lines 13-14 for one agent's leaf: q += c; m += W c;
    x' = x + gamma (m - q) - eta v, x in f32."""
    f32 = jnp.float32
    q2 = q.astype(f32) + c.astype(f32)
    m2 = m.astype(f32) + wc.astype(f32)
    x2 = x + gamma * (m2 - q2) - eta * v.astype(f32)
    kq, km = jax.random.split(key)
    return x2, store(q2, kq, plane), store(m2, km, plane)


@jax.jit
def _norm(l):
    return jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))


class Reference:
    """PORTER's first rounds for one cell, from the seed.

    Each agent's buffers live on the device that holds that agent in the
    program (agent i on ``devices[i % len(devices)]``); an agent receives
    its neighbours' increments as a copy to its own device.
    """

    def __init__(self, config: dict, traffic: dict, param_shapes,
                 precision: str = "f32", fault: str | None = None,
                 devices=None):
        if traffic["compressor"] != "top_k":
            raise ValueError("the reference follows the top_k compressor")
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.traffic = traffic
        self.shapes = param_shapes
        self.n = n = traffic["agents"]
        self.devices = list(devices or jax.devices()[: traffic["chips"]])
        w = metropolis_w(C.adjacency(traffic))
        self.gamma = mixing_gamma(w, traffic["frac"])
        if fault == "no_exchange":
            w = np.eye(n)
        self.plane = PLANES[traffic["plane_dtype"]]
        # each agent's (weight, sender) pairs
        self.recv = [[(float(w[i, k]), k) for k in range(n) if w[i, k]]
                     for i in range(n)]
        self.window = WINDOW if traffic["wire"] == "packed_bits" else 0
        self.source = C.make_source(n, traffic["batch"], traffic["seq"],
                                    C.vocab_size(config))
        self.gradient = gradient_oracle(
            config, precision, "half" if fault == "half_batch" else "all",
            float(traffic["tau"]), C.dp_sigma(traffic), bool(traffic["dp"]))

    def _on(self, a, i):
        return jax.device_put(a, self.devices[i % len(self.devices)])

    def _exchange(self, ys, qs):
        """(c_i, W c_i) of every agent for one leaf."""
        frac = float(self.traffic["frac"])
        c = [compress_leaf(frac, self.window, self.plane, y, q)
             for y, q in zip(ys, qs)]
        wc = []
        for i, pairs in enumerate(self.recv):
            ws = tuple(w for w, _ in pairs)
            wc.append(mix_leaf(ws, self.plane,
                               *[self._on(c[k], i) for _, k in pairs]))
        return c, wc

    # -- the rounds of the first chunk --------------------------------------

    def run(self, seed: int, rounds: int):
        """Losses of ``rounds`` rounds and the per-(agent, leaf) norms of
        G, v, m_v and x - x0 after them, as numpy arrays."""
        n, bf = self.n, self.plane
        kw, kr = C.stream_keys(seed)
        sr_key = jax.random.fold_in(C.seed_key(seed), 0x5E1F)
        x0 = jax.tree_util.tree_leaves(_params(self.shapes, kw))
        treedef = jax.tree_util.tree_structure(self.shapes)
        x = [[self._on(l, i) for l in x0] for i in range(n)]
        del x0
        q_x = [[l.astype(bf) for l in xi] for xi in x]
        m_x = [list(qi) for qi in q_x]
        zeros = [[jnp.zeros(l.shape, bf, device=l.devices().pop())
                  for l in xi] for xi in x]
        v, q_v, m_v, g_prev = ([list(z) for z in zeros] for _ in range(4))
        eta, losses = float(self.traffic["eta"]), []
        for t in range(rounds):
            kb, ks = jax.random.split(jax.random.fold_in(kr, t))
            tokens = self.source(kb, t)["tokens"]
            _, k_noise, _, _ = jax.random.split(ks, 4)
            agent_keys = jax.random.split(k_noise, n)
            g, loss = [], []
            for i in range(n):
                li, gi = self.gradient(treedef.unflatten(x[i]),
                                       self._on(tokens[i], i),
                                       self._on(agent_keys[i], i))
                loss.append(float(li))
                g.append([a.astype(bf) for a in jax.tree_util.tree_leaves(gi)])
                del gi
            losses.append(float(np.mean(loss)))
            kt = jax.random.fold_in(sr_key, t)
            for j in range(len(g[0])):
                kj = [jax.random.fold_in(jax.random.fold_in(kt, j), i)
                      for i in range(n)]
                c, wc = self._exchange([a[j] for a in v], [a[j] for a in q_v])
                for i in range(n):
                    v[i][j], q_v[i][j], m_v[i][j] = track_update(
                        self.gamma, self.plane, v[i][j], q_v[i][j],
                        m_v[i][j], c[i], wc[i], g[i][j], g_prev[i][j],
                        self._on(jax.random.fold_in(kj[i], 0), i))
                c, wc = self._exchange([a[j] for a in x], [a[j] for a in q_x])
                for i in range(n):
                    x[i][j], q_x[i][j], m_x[i][j] = step_update(
                        self.gamma, eta, self.plane, x[i][j], q_x[i][j],
                        m_x[i][j], c[i], wc[i], v[i][j],
                        self._on(jax.random.fold_in(kj[i], 1), i))
                del c, wc
            g_prev = g
        x0 = jax.tree_util.tree_leaves(_params(self.shapes, kw))
        norms = lambda trees: np.array([[float(_norm(l)) for l in tr]
                                        for tr in trees])
        dx = [[a - self._on(b, i) for a, b in zip(x[i], x0)]
              for i in range(n)]
        out = {"grad": norms(g_prev), "v": norms(v), "m_v": norms(m_v),
               "dx": norms(dx)}
        return np.asarray(losses), out, C.param_names(self.shapes)


@functools.partial(jax.jit, static_argnums=0)
def _params_jit(treedef_and_shapes, key):
    treedef, shapes = treedef_and_shapes
    return C.make_params(jax.tree_util.tree_unflatten(treedef, shapes), key)


def _params(shapes, key):
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return _params_jit((treedef, tuple(leaves)), key)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def tree_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst (agent, leaf) gap of norms: |‖P‖ - ‖R‖| over the larger of
    ‖R‖ and the agent's median leaf norm."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = np.median(ref, axis=1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if keep is not None:
        gap = gap[:, keep]
    return float(np.max(gap))


def moving_leaves(ref_grad: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's, on every agent."""
    med = np.median(ref_grad, axis=1, keepdims=True)
    return np.all(ref_grad >= 1e-3 * med, axis=0)


def compare(prog_losses, prog_norms: dict, ref_losses, ref_norms: dict):
    """The numbers that decide ``correct``: {name: value}."""
    pl = np.asarray(prog_losses, np.float64)
    rl = np.asarray(ref_losses, np.float64)
    nums = {"loss": float(np.max(np.abs(pl - rl) / np.abs(rl)))}
    keep = moving_leaves(ref_norms["grad"])
    for t in TREES:
        nums[t] = tree_gap(prog_norms[t], ref_norms[t],
                           keep if t == "dx" else None)
    return nums
