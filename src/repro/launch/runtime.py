"""Chunked training runtime: scan-fused comm rounds with donated state.

Every driver in the repo used to execute training as a per-step Python
loop -- one jit dispatch per comm round, host-side batch synthesis, fresh
state buffers every step, and a host sync per metric read.  PRs 1-3 fused
the *inside* of a round (pallas kernels, per-shard planes); this module
removes the overhead *between* rounds:

* :class:`BatchSource` -- the data contract: a pure, jit-traceable
  ``(key, step_index) -> batch`` so batch synthesis moves on device and
  inside the compiled program (see :mod:`repro.data.batch_source`).
* :func:`make_runner` -- jits ``lax.scan`` over ``chunk`` calls of the
  registry's uniform ``algo.step``, donates the carried state
  (``donate_argnums``), derives each round's PRNG keys from the base key
  and the absolute round index, and returns stacked per-step metrics as
  device arrays.  One dispatch, one host sync
  and one state round-trip per *chunk* instead of per round.
* :func:`run_chunked` -- drives a ``[start, steps)`` horizon chunk by
  chunk with a boundary callback (logging / checkpointing / divergence
  gating hook); at most one extra executable for the tail remainder.

Key-stream contract: round ``t``'s keys are a pure function of the base
key and the *absolute* round index,

    kb, ks = jax.random.split(jax.random.fold_in(key, t))
    state, metrics = algo.step(state, source(kb, t), ks)

so the trajectory is independent of the chunking (``chunk=k`` reproduces
``chunk=1`` bit-for-bit modulo float reassociation;
tests/test_runtime.py pins allclose at atol 1e-5 across algorithms) AND
independent of restarts: a resumed run continues the uninterrupted
stream instead of replaying the keys -- and hence the DP noise -- that
earlier rounds already consumed (which would void the accountant's
independent-composition assumption).  The base key passes through
unchanged.

Donation contract: the runner consumes its ``state`` argument -- after a
call, only the *returned* state is valid.  Checkpoint saves therefore
happen at chunk boundaries on the returned state (it is pulled to host
before the next chunk consumes it), and a state restored via
``launch/checkpoint.py`` is donated on its first chunk like any other.

Sharded launches (``launch/steps.py`` / ``launch/dryrun.py``) pass the
step's ``state_sharding`` so in/out shardings -- including the per-shard
planes of the PR-3 engine -- are preserved under the scan, plus an
optional ``batch_sharding`` constraint for the in-program batches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["BatchSource", "ChunkRunner", "make_runner", "run_chunked"]


def _dealias(state):
    """Copy repeated buffers so the state can be donated.

    The registry inits deliberately alias (PorterState's ``q_x``/``m_x``
    *are* ``x``, and the zero buffers share one array) to avoid O(n d)
    copies on the launch path; XLA refuses to donate the same buffer
    twice.  Only the first chunk ever pays the copy -- scan outputs are
    distinct buffers, so later calls just walk the tree.
    """
    seen = set()

    def buffer_key(leaf):
        # every shard's buffer: two objects that share buffers (an aliased
        # init, sharded or not) must not both be donated
        try:
            return tuple(s.data.unsafe_buffer_pointer()
                         for s in leaf.addressable_shards)
        except Exception:  # deleted or not yet materialized: identity
            return id(leaf)

    def dedupe(leaf):
        if not isinstance(leaf, jax.Array):
            return leaf
        k = buffer_key(leaf)
        if k in seen:
            return jnp.array(leaf)
        seen.add(k)
        return leaf

    return jax.tree_util.tree_map(dedupe, state)


class BatchSource(Protocol):
    """Pure, jit-traceable batch synthesis: ``(key, step_index) -> batch``.

    ``key`` is a fresh PRNG key for this round; ``step_index`` is the
    absolute round index as a traced int32 scalar (deterministic sources
    index with it, iid sources ignore it).  The returned batch must be
    agent-stacked exactly like the batches the per-step loops fed
    ``algo.step`` -- leading dim ``n_agents``.
    """

    def __call__(self, key: jax.Array, step: jax.Array) -> Any: ...


@dataclasses.dataclass
class ChunkRunner:
    """A compiled chunk program: ``(state, key, start) -> (state, key,
    stacked metrics)``.

    ``state`` is DONATED: after a call only the returned state is valid.
    ``start`` is a traced scalar, so one executable serves every chunk
    offset (``cache_size()`` stays 1 per runner).
    """

    chunk: int
    donate: bool
    jitted: Any

    def __call__(self, state, key, start: int = 0):
        with jax.profiler.TraceAnnotation("runner.dispatch", start=start):
            if self.donate:
                state = _dealias(state)
            return self.jitted(state, key, jnp.asarray(start, jnp.int32))

    def lower(self, state_shapes, key_shape=None):
        """Abstract lowering (dry-run path): no buffer is materialized."""
        if key_shape is None:
            key_shape = jax.ShapeDtypeStruct((2,), jnp.uint32)
        start = jax.ShapeDtypeStruct((), jnp.int32)
        return self.jitted.lower(state_shapes, key_shape, start)

    def cache_size(self) -> Optional[int]:
        """Compiled-executable count (None if this jax can't report it)."""
        getter = getattr(self.jitted, "_cache_size", None)
        return getter() if getter is not None else None


def make_runner(algo, source: BatchSource, chunk: int, *, donate: bool = True,
                state_sharding=None, batch_sharding=None) -> ChunkRunner:
    """Build the scan-fused runner over ``chunk`` rounds of ``algo.step``.

    algo: a registry :class:`~repro.core.registry.Algorithm` (anything with
      the uniform ``step(state, batch, key) -> (state, metrics)``), or the
      bare step function itself.
    source: a :class:`BatchSource`; batches are synthesized inside the
      compiled program, so a chunk costs one dispatch and zero host round
      trips for data.
    donate: donate the carried state (``donate_argnums``) -- the chunk
      updates state in place instead of allocating a second copy.
    state_sharding / batch_sharding: sharded-launch hooks.  The state
      sharding is applied to both the input and output state (preserved
      under the scan); the batch sharding is applied as a constraint on
      each synthesized batch.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    step = getattr(algo, "step", algo)

    def run_chunk(state, key, start):
        def body(st, t):
            # keys are a pure function of (base key, absolute round): the
            # stream is chunking- and restart-invariant (no DP-noise
            # replay on resume)
            kb, ks = jax.random.split(jax.random.fold_in(key, t))
            with jax.named_scope("runner.batch"):
                batch = source(kb, t)
                if batch_sharding is not None:
                    batch = jax.lax.with_sharding_constraint(batch,
                                                             batch_sharding)
            st, metrics = step(st, batch, ks)
            return st, metrics

        state, metrics = jax.lax.scan(
            body, state, start + jnp.arange(chunk, dtype=jnp.int32))
        return state, key, metrics

    kw = {}
    if state_sharding is not None:
        mesh = jax.tree_util.tree_leaves(state_sharding)[0].mesh
        repl = NamedSharding(mesh, P())
        # repl is a pytree prefix covering the key/start inputs and the
        # key + stacked-metrics outputs (scalars stay replicated)
        kw = dict(in_shardings=(state_sharding, repl, repl),
                  out_shardings=(state_sharding, repl, repl))
    jitted = jax.jit(run_chunk, donate_argnums=(0,) if donate else (), **kw)
    return ChunkRunner(chunk=chunk, donate=donate, jitted=jitted)


def run_chunked(algo, source: BatchSource, state, key, steps: int, *,
                chunk: int, start: int = 0, donate: bool = True,
                state_sharding=None, batch_sharding=None,
                on_chunk: Optional[Callable] = None) -> Tuple[Any, Any]:
    """Run rounds ``[start, steps)`` in scan-fused chunks of ``chunk``.

    ``on_chunk(t0, t1, state, metrics)`` fires at every chunk boundary with
    the post-chunk state and the stacked (length ``t1 - t0``) metrics for
    rounds ``[t0, t1)`` -- still device arrays, so the callback decides
    when to sync.  Returning ``False`` stops the run at that boundary
    (divergence gating).  The callback must not keep a reference to
    ``state`` past its return: the next chunk donates it.

    Compiles one executable for the main chunk size plus at most one for
    the tail remainder.  Returns the final ``(state, key)``.
    """
    runners = {}
    t = start
    while t < steps:
        size = min(chunk, steps - t)
        runner = runners.get(size)
        if runner is None:
            runner = runners[size] = make_runner(
                algo, source, size, donate=donate,
                state_sharding=state_sharding, batch_sharding=batch_sharding)
        state, key, metrics = runner(state, key, t)
        t += size
        if on_chunk is not None:
            with jax.profiler.TraceAnnotation("runner.on_chunk",
                                              start=t - size):
                go_on = on_chunk(t - size, t, state, metrics)
            if go_on is False:
                break
    return state, key
