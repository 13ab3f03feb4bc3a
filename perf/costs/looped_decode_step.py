"""Bytes one decode step of the paged engine must read when the layer
stack is looped (``total_ut_steps`` passes over shared weights), from
shapes alone: the bound memory bandwidth sets on a step."""
from typing import Any, Dict

from perf.costs.model import attn_out
from perf.costs.model import kv_out


def looped_decode_step_bytes(cfg: Dict[str, Any], weight_bytes: int,
                             kv_bytes: int, rows: float,
                             kv_tokens: float) -> float:
    """Bytes one decode step must read: the layers' matmul weights
    with their scales ONCE A PASS (2.5 GB of them cannot stay on the
    chip between passes), the head once, the valid keys and values of
    the active rows with their scales in every one of the passes x
    layers KV entries, and one embedding row per active row."""
    passes, n_layers = cfg['total_ut_steps'], cfg['num_hidden_layers']
    d, ffn = cfg['hidden_size'], cfg['intermediate_size']
    layer = (d * attn_out(cfg) + 2 * d * kv_out(cfg) +
             attn_out(cfg) * d + 3 * d * ffn)
    layer_channels = 2 * attn_out(cfg) + 2 * kv_out(cfg) + 2 * ffn + d
    weights = (passes * n_layers * layer +
               d * cfg['vocab_size']) * weight_bytes
    scales = (passes * n_layers * layer_channels +
              cfg['vocab_size']) * 2 if weight_bytes == 1 else 0
    kv_heads = cfg['num_key_value_heads']
    per_token = passes * n_layers * 2 * (
        kv_out(cfg) * kv_bytes + (kv_heads * 2 if kv_bytes == 1 else 0))
    return weights + scales + kv_tokens * per_token + rows * d * 2
