"""The host half of the sampling subsystem (docs/sampling.md):
structured decoding. ``grammar`` compiles JSON-schema / regex
grammars (cached by grammar hash) to a character DFA and walks it
against the token vocabulary to produce per-request allowed-token
masks, which the jitted steps gather by traced index.

The device half — the counter-keyed PRNG, per-row sampling and the
single speculative-acceptance rule that the model steps trace — is
``skypilot_tpu/ops/sampling/``, below ``models/decode.py``.
"""
from skypilot_tpu.serve.sampling.grammar import (CompiledGrammar,
                                                 GrammarError,
                                                 compile_grammar,
                                                 grammar_hash)

__all__ = [
    'CompiledGrammar', 'GrammarError', 'compile_grammar',
    'grammar_hash',
]
