"""Bytes one decode step of the paged engine must read for a stack of
latent-attention layers over several residual streams, dense layers
first and expert layers held whole after them (``xing4_0``), from
shapes and counts alone: the bound memory bandwidth sets on a step.
It counts the work, not the implementation: whatever computes the
layer has to read this much."""
from typing import Any, Dict


def latent_moe_decode_step_bytes(cfg: Dict[str, Any], weight_bytes: int,
                                 rows: float, context_tokens: float,
                                 experts_hit_share: float) -> float:
    """Bytes one decode step must read:

    - per layer, once: the five MLA projections (each matmul weight
      with its per-channel scales), the four norm vectors and the two
      stream mixers (bf16);
    - per dense layer the gated MLP of ``intermediate_size``; per
      expert layer the router and its bias (bf16), the shared expert,
      and of the routed experts only the share that got a token this
      step (``experts_hit_share``, the program's count of experts hit
      over experts held, a layer and step);
    - the untied head once (int8 with scales), and one embedding row
      an active row (bf16);
    - the latent rows: ``context_tokens`` cached positions, summed
      over the active rows, EACH ROW'S OWN LENGTH, in every layer's
      entry, ``kv_lora_rank + qk_rope_head_dim`` bf16 values each (a
      shared document is counted once a row that reads it: the rows
      attend it separately)."""
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    rq, rkv = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd, n = cfg['v_head_dim'], cfg['hc_mult']
    n_layers = cfg['num_hidden_layers']
    n_dense = cfg['first_k_dense_replace']
    scale = 2 if weight_bytes == 1 else 0       # bf16, a channel

    def matmul(fan_in, fan_out):
        return fan_in * fan_out * weight_bytes + fan_out * scale

    def gated(width):
        return 2 * matmul(d, width) + matmul(width, d)

    attention = (matmul(d, rq) + matmul(rq, heads * (nope + rope)) +
                 matmul(d, rkv + rope) +
                 matmul(rkv, heads * (nope + vd)) +
                 matmul(heads * vd, d))
    norms = (2 * d + rq + rkv) * 2
    mixers = 2 * (n * d + 1) * (2 * n + n * n) * 2 + 2 * 3 * 2
    every = attention + norms + mixers
    experts = cfg['n_routed_experts']
    moe = (d * experts * 2 + experts * 2 +
           cfg['n_shared_experts'] * gated(cfg['moe_intermediate_size'])
           + experts * experts_hit_share *
           gated(cfg['moe_intermediate_size']))
    head = matmul(d, cfg['vocab_size']) + d * 2 + rows * d * 2
    latent = context_tokens * (rkv + rope) * 2 * n_layers
    return (n_layers * every + n_dense * gated(cfg['intermediate_size'])
            + (n_layers - n_dense) * moe + head + latent)
