"""Bytes one decode step of the paged engine must read for a stack of
window and global layers with a chip's share of an expert layer
(``cohere2_moe``), from shapes and counts alone: the bound memory
bandwidth sets on a step. It counts the work, not the implementation:
whatever computes the layer has to read this much."""
from typing import Any, Dict


def window_moe_decode_step_bytes(cfg: Dict[str, Any], weight_bytes: int,
                                 kv_bytes: int, rows: float,
                                 global_tokens: float,
                                 window_tokens: float,
                                 experts_hit_share: float) -> float:
    """Bytes one decode step must read:

    - per layer, once: the attention projections, the router (bf16),
      the shared experts, each matmul weight with its per-channel
      scales; of the routed experts held here only the share that got
      a token this step (``experts_hit_share``, the program's count of
      experts hit over experts held, a layer and step);
    - the head once: the rows held of the tied embedding (bf16), and
      one embedding row an active row;
    - the valid keys and values with their scales: ``global_tokens``
      cached positions in every global layer's entry and
      ``window_tokens`` in every window layer's (distinct blocks the
      rows reference, a shared document's counted once: a lower
      bound on what 32 rows over 4 documents read)."""
    d, ffn = cfg['hidden_size'], cfg['intermediate_size']
    heads, kv_heads, hd = (cfg['num_attention_heads'],
                           cfg['num_key_value_heads'], cfg['head_dim'])
    n_layers = cfg['num_hidden_layers']
    kinds = cfg['layer_types'][:n_layers]
    n_window = sum(k == 'sliding_attention' for k in kinds)
    q_out, kv_out = heads * hd, kv_heads * hd
    scale = 2 if weight_bytes == 1 else 0       # bf16, a channel

    def matmul(fan_in, fan_out):
        return fan_in * fan_out * weight_bytes + fan_out * scale

    attention = (matmul(d, q_out) + 2 * matmul(d, kv_out) +
                 matmul(q_out, d))
    one_expert = 2 * matmul(d, ffn) + matmul(ffn, d)
    shared = cfg['num_shared_experts'] * one_expert
    router = d * cfg['published']['num_experts'] * 2
    routed = cfg['num_experts'] * experts_hit_share * one_expert
    layer = attention + shared + router + routed + d * 2
    head = cfg['vocab_size'] * d * 2 + rows * d * 2
    per_token = 2 * (kv_out * kv_bytes +
                     (kv_heads * 2 if kv_bytes == 1 else 0))
    kv = per_token * (window_tokens * n_window +
                      global_tokens * (n_layers - n_window))
    return n_layers * layer + head + kv
