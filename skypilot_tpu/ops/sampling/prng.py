"""Counter-based per-request PRNG (the batch-invariance anchor).

Every random draw the serve plane makes is keyed by
``(request_seed, absolute_position)`` and NOTHING else. The key is a
pure function of those two integers — not of the batch width, not of
the slot index, not of how many draws happened before (there is no
split chain to advance). Consequences, all load-bearing:

- **batch invariance**: a request sees the same draws whether it
  decodes alone or next to 15 neighbors;
- **preempt/resume exactness**: resume re-prefills prompt+generated
  and continues at the same absolute positions, so the continuation
  re-derives the identical keys;
- **spec-on == spec-off**: the verify step draws for position ``p``
  with the same key plain decode would have used at position ``p``
  (see accept.py for why that makes speculative sampling bitwise
  equal to plain sampling).

Keys are derived with ``jax.random`` threefry machinery from TRACED
seed/position arrays, so they live inside the jitted step functions —
one executable serves every request. This module is the ONLY place
allowed to construct PRNG keys inside the serve plane's jitted steps
(the ``serve-jit-prng`` skylint rule enforces it).
"""
import jax
import jax.numpy as jnp


def row_key(seed: jax.Array, position: jax.Array) -> jax.Array:
    """Key for the single draw at ``(seed, position)``.

    ``seed``/``position`` are (traced) int32 scalars. Counter-based:
    ``fold_in`` of the position into the request's root key — stateless,
    order-free, identical wherever it is evaluated.
    """
    root = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    return jax.random.fold_in(root, jnp.asarray(position, jnp.int32))


def row_keys(seeds: jax.Array, positions: jax.Array) -> jax.Array:
    """Vectorized ``row_key`` over per-row [B] seed/position arrays."""
    return jax.vmap(row_key)(seeds, positions)
