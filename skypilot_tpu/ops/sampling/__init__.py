"""The device half of the sampling subsystem (docs/sampling.md): what
the paged engine's model steps (``models/decode.py``) trace into
their programs. The contract is **batch invariance** — a request's
sampled output depends only on its own ``(seed, position)`` pairs,
never on its batch neighbors, its slot assignment, or whether it was
preempted and resumed.

- ``prng``   — counter-based per-row PRNG: every random draw is keyed
  by ``(request_seed, absolute_position)`` alone, derived INSIDE the
  jitted step functions from traced per-row arrays. No host RNG, no
  split-chain whose value depends on how many draws other rows made.
- ``sample`` — per-row temperature/top-p sampling usable inside the
  jitted decode/prefill/verify steps (traced per-row knob arrays, one
  executable for every request mix; ``temperature <= 0`` rows reduce
  bitwise to the greedy argmax) plus the grammar-mask gather.
- ``accept`` — THE single speculative-acceptance implementation
  (``accept_tokens``): the Chen et al. 2023 rejection-sampling rule,
  realized by maximal coupling so spec-on output is bitwise identical
  to spec-off output (see accept.py for the math).

The host half (grammars compiled to token masks) is
``serve/sampling/grammar.py``. The contract is machine-checked: the
``serve-jit-prng`` skylint rule forbids PRNG-key construction / host
RNG inside the serve plane's jitted steps outside this package.
"""
from skypilot_tpu.ops.sampling.accept import accept_tokens
from skypilot_tpu.ops.sampling.prng import row_key, row_keys
from skypilot_tpu.ops.sampling.sample import (gather_masks,
                                              sample_first,
                                              sample_rows,
                                              verify_targets)

__all__ = [
    'accept_tokens', 'row_key', 'row_keys', 'gather_masks',
    'sample_first', 'sample_rows', 'verify_targets',
]
