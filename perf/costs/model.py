"""Shapes every cost function shares, and the train step's operations
per token. Operations and bytes are computed from shapes: the least
the algorithm needs, never what an implementation happens to do
(recomputed operations, padded views and re-read weights do not
count). Every function takes the configuration's published keys."""
from typing import Any, Dict


def attn_out(cfg: Dict[str, Any]) -> int:
    return cfg['hidden_size']  # heads * head size, Mistral/Llama


def kv_out(cfg: Dict[str, Any]) -> int:
    head = cfg['hidden_size'] // cfg['num_attention_heads']
    return cfg['num_key_value_heads'] * head


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul for every token: the
    layers' projections and the output head. The embedding table is a
    gather, not a matmul, and is left out."""
    d, ffn = cfg['hidden_size'], cfg['intermediate_size']
    layer = (d * attn_out(cfg) + 2 * d * kv_out(cfg) +
             attn_out(cfg) * d + 3 * d * ffn)
    return cfg['num_hidden_layers'] * layer + d * cfg['vocab_size']


def attention_matmul_flops(cfg: Dict[str, Any], batch: int, seq: int
                           ) -> float:
    """One attention matmul (QK^T, or PV, or one of the backward's)
    over a causal ``seq x seq`` map, all heads of ONE layer: half the
    square is masked and is not counted."""
    return 2.0 * batch * attn_out(cfg) * seq * seq / 2.0


def train_flops_per_token(cfg: Dict[str, Any], seq: int,
                          frozen_base: bool) -> float:
    """Forward and backward per token. A frozen base (LoRA) needs no
    weight gradients: 2 + 2 per matmul parameter, not 2 + 4.
    Attention: 2 matmuls forward and 4 backward over the causal half.
    The adapters' own matmuls (rank 16) are under 0.1% and left out."""
    per_param = 4.0 if frozen_base else 6.0
    attn = 6 * attention_matmul_flops(cfg, 1, seq) / seq
    return (per_param * matmul_params(cfg) +
            cfg['num_hidden_layers'] * attn)
