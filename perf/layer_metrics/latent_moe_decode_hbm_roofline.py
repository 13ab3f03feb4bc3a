"""``latent_moe_decode_hbm_roofline``: a decode step's share (%) of the
bound that memory bandwidth sets, for a stack of latent-attention
layers with dense layers first and the experts held whole - the bytes
one step must read (perf/costs/latent_moe_decode_step.py: the MLA
projections, mixers, dense and shared leaves and the head once, the
experts that were HIT, and the latent rows of each row's OWN length,
all by the program's counters) over the chip's bandwidth, over the
device time of a decode step. Decode is one unit, as in
``decode_hbm_roofline``. None where the configuration has no latent
cache, the program no such counters or the trace no decode program."""
from perf.costs import latent_moe_decode_step
from perf.lib import readers


def reduce(trace, records):
    model = records['model']
    if 'kv_lora_rank' not in model:
        return None
    step_ms = readers.xla_module_ms(
        {'module': '^jit_decode_steps_paged$',
         'per': 'steps_per_dispatch'}, trace, records)
    reg = records.get('registry')
    if step_ms is None or reg is None:
        return None
    deltas = [reg.delta(name) for name in (
        'skytpu_batch_mla_absorbed_row_steps_total',
        'skytpu_batch_mla_absorbed_context_tokens_total',
        'skytpu_batch_decode_dispatches_total',
        'skytpu_batch_moe_experts_hit_total',
        'skytpu_batch_moe_experts_held_total')]
    if any(d is None or not d[0] for d in deltas):
        return None
    row_steps, context, dispatches, hit, held = (d[0] for d in deltas)
    facts = records['facts']
    steps = dispatches * facts['steps_per_dispatch']
    need = latent_moe_decode_step.latent_moe_decode_step_bytes(
        model, facts['weight_bytes'], rows=row_steps / steps,
        context_tokens=context / steps, experts_hit_share=hit / held)
    return 100.0 * need / records['peaks']['hbm_bytes_per_s'] / \
        (step_ms * 1e-3)
