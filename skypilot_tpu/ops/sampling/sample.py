"""Per-row sampled decode inside the jitted step functions.

All knobs are TRACED per-row arrays — temperature [B], top_p [B],
seed [B] — so one executable serves every request mix; greedy rows
ride along with ``temperature == 0`` and reduce bitwise to the argmax
the exactness suite certifies. Randomness comes exclusively from the
counter-based keys in ``prng`` (one draw per ``(seed, position)``).

Grammar masks arrive as a ``[M, V]`` bool table plus per-row traced
indices and are gathered in-jit (``gather_masks``): row 0 of the
table is the all-allowed mask, so unconstrained rows share index 0
and the executable shape never depends on how many requests are
constrained.
"""
from typing import Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.ops.sampling import prng

# Matches models/decode.py's _NEG_INF (finite: arithmetic on it stays
# NaN-free through softmax/cumsum).
NEG_INF = -1e30


def gather_masks(mask_table: jax.Array,
                 mask_idx: jax.Array) -> jax.Array:
    """Gather per-row [B, ...] allowed-token masks out of a [M, ...]
    table by traced per-row index (the in-jit half of the grammar
    pipeline — the table/indices are built host-side by the walker)."""
    return jnp.take(mask_table, mask_idx, axis=0)


def _filter_top_p_row(logits: jax.Array,
                      top_p: jax.Array) -> jax.Array:
    """Per-row nucleus filter with a DYNAMIC top_p — the [V]-vector
    analog of models/decode._filter_top_p (same math: keep the
    smallest descending-prob prefix whose cumulative mass reaches
    top_p; the top-1 token is always kept)."""
    top_p = jnp.maximum(jnp.asarray(top_p, jnp.float32), 1e-6)
    sorted_desc = jnp.flip(jnp.sort(logits))
    probs = jax.nn.softmax(sorted_desc)
    cum = jnp.cumsum(probs)
    # top_p = 1 keeps every token: the float32 running sum reaches 1
    # a few tokens before the end, and would cut a tail of about 1e-6
    # of the mass that no nucleus was asked to cut.
    outside = ((cum - probs) >= top_p) & (top_p < 1.0)
    kth = jnp.where(outside, jnp.inf, sorted_desc).min()
    return jnp.where(logits < kth, NEG_INF, logits)


def _filter_rows(logits: jax.Array, top_ps: jax.Array) -> jax.Array:
    """``_filter_top_p_row`` over rows [N, V], skipped as a whole
    where no row asks for a nucleus (every ``top_p`` is 1, at which
    the filter keeps every token anyway): a full sort of the
    vocabulary a row and draw is most of a sampled step's sampler on
    the chip, and a mix at top_p 1 never needs it."""
    return jax.lax.cond(
        jnp.any(top_ps < 1.0),
        lambda: jax.vmap(_filter_top_p_row)(logits, top_ps),
        lambda: logits)


def _draw_row(logits: jax.Array, filtered: jax.Array,
              temperature: jax.Array, seed: jax.Array,
              position: jax.Array) -> jax.Array:
    """One row: greedy argmax of ``logits`` when ``temperature <=
    0`` (bitwise the pre-sampling engine behavior), else the
    temperature categorical over ``filtered`` (the row's logits
    through its nucleus) keyed (seed, position)."""
    t_safe = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    key = prng.row_key(seed, position)
    sampled = jax.random.categorical(key, filtered / t_safe)
    return jnp.where(temperature <= 0.0, logits.argmax(-1),
                     sampled).astype(jnp.int32)


def sample_rows(logits: jax.Array, temperatures: jax.Array,
                top_ps: jax.Array, seeds: jax.Array,
                positions: jax.Array,
                allowed: Optional[jax.Array] = None) -> jax.Array:
    """Per-row next-token selection for the jitted decode step.

    ``logits`` [B, V]; ``temperatures``/``top_ps``/``seeds``/
    ``positions`` [B] traced; ``allowed`` optional [B, V] bool.
    Returns int32 [B]: the greedy argmax where ``temperature <= 0``,
    else top-p + temperature categorical keyed (seed, position).
    Each row is independent — nothing carries cross-row state but
    the one question whether ANY row wants a nucleus
    (``_filter_rows``), which changes no row's result: that is the
    batch-invariance property.
    """
    logits = logits.astype(jnp.float32)
    if allowed is not None:
        logits = jnp.where(allowed, logits, NEG_INF)
    return jax.vmap(_draw_row)(logits, _filter_rows(logits, top_ps),
                               temperatures, seeds, positions)


def sample_first(logits: jax.Array, temperature: jax.Array,
                 top_p: jax.Array, seed: jax.Array,
                 position: jax.Array,
                 allowed: Optional[jax.Array] = None) -> jax.Array:
    """First-token selection from prefill logits ([1, V] — the
    chunked-prefill step projects only the last real position).
    Same keying as decode at the same absolute position, so the
    prompt/decode boundary is invisible to the (seed, position)
    contract. Returns an int32 scalar."""
    one = jnp.reshape
    return sample_rows(
        logits[:1], one(temperature, (1,)), one(top_p, (1,)),
        one(seed, (1,)), one(position, (1,)),
        None if allowed is None else allowed[None])[0]


def verify_targets(logits: jax.Array, temperatures: jax.Array,
                   top_ps: jax.Array, seeds: jax.Array,
                   pos: jax.Array,
                   allowed: Optional[jax.Array] = None) -> jax.Array:
    """Target-model token realizations for the verify step.

    ``logits`` [B, W, V] — row r's column j holds the target logits
    at absolute position ``pos[r] + j``. Each (row, column) draws
    with the SAME counter key plain decode would use at that
    position, so the realized token x*_j is exactly the token plain
    sampled decode would emit there — the maximal-coupling half of
    the speculative-sampling acceptance rule (accept.py).

    ``allowed`` optional [B, W, V]: per-position grammar masks walked
    host-side along the draft path. Returns int32 [B, W].
    """
    b, w, v = logits.shape
    positions = pos[:, None] + jnp.arange(w, dtype=pos.dtype)[None, :]

    def lanes(knob):
        return jnp.repeat(knob, w)

    return sample_rows(
        logits.reshape(b * w, v), lanes(temperatures), lanes(top_ps),
        lanes(seeds), positions.reshape(-1),
        None if allowed is None else allowed.reshape(b * w, v)
    ).reshape(b, w)
