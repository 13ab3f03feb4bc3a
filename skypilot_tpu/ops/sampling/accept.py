"""THE single speculative-acceptance implementation.

Distribution math (Chen et al. 2023, "Accelerating Large Language
Model Decoding with Speculative Sampling"): a draft token x drawn
from proposal q is accepted with probability ``min(1, p(x)/q(x))``
against the target distribution p; on rejection the emitted token is
resampled from the residual ``max(0, p - q)`` renormalized. The
engine's n-gram drafter is DETERMINISTIC — q is a point mass at the
draft d — so the rule specializes to: accept d with probability
``p(d)``; on reject, resample from p conditioned on ``x != d``
(which is exactly the normalized residual ``max(0, p - 1[x=d])``).

This file implements that rule by **maximal coupling**: draw
``x* ~ p`` once with the counter key plain decode would use at the
same absolute position (sample.verify_targets), then

- accept  iff ``d == x*``   — an event of probability exactly p(d);
- emit ``x*`` always        — on accept that IS d; on reject x* is
  distributed as p given ``x != d``, i.e. the residual.

Coupling the accept draw and the resample draw to the single plain-
decode draw preserves the target distribution EXACTLY (it is the
same random variable) and buys the stronger engine contract for
free: spec-on output is bitwise identical to spec-off output, at any
temperature — greedy rows reduce to argmax realizations, where this
rule degenerates to the old ``greedy_accept`` leading-run count.

``accept_tokens`` is lint-enforced as the ONE acceptance
implementation in the tree (tests/test_speculative.py
TestAcceptanceLint): any other draft-vs-target comparison is a
second acceptance path the exactness suite does not cover.
"""
import jax
import jax.numpy as jnp


def accept_tokens(tokens: jax.Array, preds: jax.Array,
                  n_real: jax.Array) -> jax.Array:
    """Per-row count of accepted draft tokens.

    ``tokens`` [B, W]: column 0 is the row's committed last token,
    columns 1.. are the drafts. ``preds`` [B, W]: the target-model
    realizations x* per position (argmax for greedy rows, counter-
    keyed samples for sampled rows — sample.verify_targets).
    ``n_real`` [B]: 1 + number of real drafts (0 = parked row).

    Row r accepts the longest leading run of drafts whose token
    equals the target realization at its position — the maximal-
    coupling acceptance above. Everything after the first mismatch
    is position-rolled-back by the engine; the emitted tokens are
    ``preds[r, :accepted+1]`` (accepted drafts == the realizations,
    plus the bonus token at the first mismatch or the end).
    """
    w = tokens.shape[1]
    ok = tokens[:, 1:] == preds[:, :-1]
    is_draft = jnp.arange(w - 1, dtype=jnp.int32)[None, :] < \
        (n_real - 1)[:, None]
    lead = jnp.cumprod((ok & is_draft).astype(jnp.int32), axis=1)
    return lead.sum(axis=1)
