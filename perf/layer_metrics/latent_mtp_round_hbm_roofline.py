"""``latent_mtp_round_hbm_roofline``: a drafting round's share (%) of
the bound that memory bandwidth sets, for a stack of latent-attention
layers whose own next-token-prediction module drafts on the device -
the bytes one round must read (perf/costs/latent_mtp_round.py: every
leaf of the main layers and of the module's once, the head twice, the
experts that were HIT, and the latent rows of each row's OWN length
at the two main positions and the module's pairs, all by the
program's counters) over the chip's bandwidth, over the device time
of a round (``jit_mtp_rounds_paged`` over its rounds). None where the
configuration has no module, the program no such counters or the
trace no rounds program."""
from perf.costs import latent_mtp_round
from perf.lib import readers


def reduce(trace, records):
    model = records['model']
    if not model.get('num_nextn_predict_layers') or \
            'kv_lora_rank' not in model:
        return None
    round_ms = readers.xla_module_ms(
        {'module': '^jit_mtp_rounds_paged$',
         'per': 'steps_per_dispatch'}, trace, records)
    reg = records.get('registry')
    if round_ms is None or reg is None:
        return None
    deltas = [reg.delta(name) for name in (
        'skytpu_batch_mla_absorbed_row_steps_total',
        'skytpu_batch_mla_absorbed_context_tokens_total',
        'skytpu_batch_decode_dispatches_total',
        'skytpu_batch_mtp_row_rounds_total',
        'skytpu_batch_moe_experts_hit_total',
        'skytpu_batch_moe_experts_held_total')]
    if any(d is None or not d[0] for d in deltas):
        return None
    positions, context, dispatches, row_rounds, hit, held = (
        d[0] for d in deltas)
    facts = records['facts']
    rounds = dispatches * facts['steps_per_dispatch']
    # A row-round has 2 main positions and tokens / row_rounds pairs.
    need = latent_mtp_round.latent_mtp_round_bytes(
        model, facts['weight_bytes'], rows=positions / rounds,
        context_tokens=context / rounds, experts_hit_share=hit / held,
        main_share=2.0 * row_rounds / positions)
    return 100.0 * need / records['peaks']['hbm_bytes_per_s'] / \
        (round_ms * 1e-3)
