"""Bytes one drafting ROUND of the paged engine must read for a stack
of latent-attention layers (a dense layer first, expert layers held
whole) whose own next-token-prediction module drafts
(``joyai_llm_flash``; ``models/decode.mtp_rounds_paged``), from
shapes and counts alone: the bound memory bandwidth sets on a round.
It counts the work, not the implementation: whatever computes a round
has to read this much. A round is the main stack on two query
positions a row, the head on both, the module on the one or two
pairs the commit completed, and the head again on the module's last
pair."""
from typing import Any, Dict


def latent_mtp_round_bytes(cfg: Dict[str, Any], weight_bytes: int,
                           rows: float, context_tokens: float,
                           experts_hit_share: float,
                           main_share: float) -> float:
    """Bytes one round must read:

    - per main layer and for the module's one layer, once: the five
      MLA projections (each matmul weight with its per-channel
      scales) and the four norm vectors (bf16);
    - the dense layer's gated MLP of ``intermediate_size``; per
      expert layer (the module's too) the router and its bias
      (bf16), the shared expert, and of the routed experts only the
      share that got a pair this round (``experts_hit_share``, the
      program's count of experts hit over experts held, a layer and
      round, the module's layer among them);
    - the module's projection [2 d, d] and its three norms;
    - the untied head TWICE (the main logits, the module's), and
      three embedding rows an active row (bf16: two inputs of the
      main stack, the module's pairs share them);
    - the latent rows: ``context_tokens`` cached positions, summed
      over the round's row-positions (the program's count: each of a
      row's two main positions and its one or two pairs reads the
      row's OWN length), ``kv_lora_rank + qk_rope_head_dim`` bf16
      values each, in every main layer's entry for the main
      positions and in the module's for the pairs. The counter sums
      both kinds; ``main_share`` of it is the main positions' (2 of
      the 2 + tokens-a-round positions a row-round has, which all
      read about the same length), ``rows`` being the active rows'
      row-positions a round."""
    d, heads = cfg['hidden_size'], cfg['num_attention_heads']
    rq, rkv = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    vd = cfg['v_head_dim']
    n_layers = cfg['num_hidden_layers']
    n_dense = cfg['first_k_dense_replace']
    n_mtp = cfg['num_nextn_predict_layers']
    scale = 2 if weight_bytes == 1 else 0       # bf16, a channel

    def matmul(fan_in, fan_out):
        return fan_in * fan_out * weight_bytes + fan_out * scale

    def gated(width):
        return 2 * matmul(d, width) + matmul(width, d)

    attention = (matmul(d, rq) + matmul(rq, heads * (nope + rope)) +
                 matmul(d, rkv + rope) +
                 matmul(rkv, heads * (nope + vd)) +
                 matmul(heads * vd, d))
    every = attention + (2 * d + rq + rkv) * 2
    experts = cfg['n_routed_experts']
    moe = (d * experts * 2 + experts * 2 +
           cfg['n_shared_experts'] * gated(cfg['moe_intermediate_size'])
           + experts * experts_hit_share *
           gated(cfg['moe_intermediate_size']))
    module = n_mtp * (matmul(2 * d, d) + 3 * d * 2)
    head = 2 * matmul(d, cfg['vocab_size']) + d * 2 + rows * d * 2
    latent = context_tokens * (rkv + rope) * 2 * (
        main_share * n_layers + (1.0 - main_share) * n_mtp)
    return ((n_layers + n_mtp) * every +
            n_dense * gated(cfg['intermediate_size']) +
            (n_layers - n_dense + n_mtp) * moe + module + head +
            latent)
