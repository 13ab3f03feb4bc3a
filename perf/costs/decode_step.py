"""Bytes one decode step of the paged engine must read, from shapes
alone: the bound memory bandwidth sets on a step."""
from typing import Any, Dict

from perf.costs.model import attn_out
from perf.costs.model import kv_out
from perf.costs.model import matmul_params


def decode_step_bytes(cfg: Dict[str, Any], weight_bytes: int,
                      kv_bytes: int, rows: float, kv_tokens: float
                      ) -> float:
    """Bytes one decode step must read: every matmul weight and the
    head with their scales, the valid keys and values of the active
    rows with theirs, and one embedding row per active row."""
    d, n_layers = cfg['hidden_size'], cfg['num_hidden_layers']
    out_channels = n_layers * (2 * attn_out(cfg) + 2 * kv_out(cfg)
                               + 2 * cfg['intermediate_size'] + d
                               ) + cfg['vocab_size']
    weights = matmul_params(cfg) * weight_bytes
    scales = out_channels * 2 if weight_bytes == 1 else 0
    kv_heads = cfg['num_key_value_heads']
    per_token = n_layers * 2 * (kv_out(cfg) * kv_bytes +
                                (kv_heads * 2 if kv_bytes == 1 else 0))
    return weights + scales + kv_tokens * per_token + rows * d * 2
