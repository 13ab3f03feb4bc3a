"""``window_moe_decode_hbm_roofline``: a decode step's share (%) of the
bound that memory bandwidth sets, for a stack of window and global
layers with a chip's share of the experts - the bytes one step must
read (perf/costs/window_moe_decode_step.py: attention, router, shared
experts and head once, the held experts that were HIT, by the
program's counters, the valid keys and values of each block group)
over the chip's bandwidth, over the device time of a decode step.
Decode is one unit, as in ``decode_hbm_roofline``. None where the
configuration has no window layers, the program no expert counters or
the trace no decode program."""
from perf.costs import window_moe_decode_step
from perf.lib import readers


def reduce(trace, records):
    model = records['model']
    if 'sliding_window' not in model or 'published' not in model:
        return None
    step_ms = readers.xla_module_ms(
        {'module': '^jit_decode_steps_paged$',
         'per': 'steps_per_dispatch'}, trace, records)
    reg = records.get('registry')
    if step_ms is None or reg is None:
        return None
    rows = reg.samples.get('skytpu_batch_slots_occupied')
    blocks = reg.samples.get('skytpu_batch_kv_blocks_used')
    window = reg.samples.get('skytpu_batch_kv_window_blocks_used')
    hit = reg.delta('skytpu_batch_moe_experts_hit_total')
    held = reg.delta('skytpu_batch_moe_experts_held_total')
    if not rows or not blocks or not window or hit is None \
            or not held or not held[0]:
        return None
    facts = records['facts']

    def mean(xs):
        return sum(xs) / len(xs)

    need = window_moe_decode_step.window_moe_decode_step_bytes(
        model, facts['weight_bytes'], facts['kv_bytes'],
        rows=mean(rows),
        global_tokens=(mean(blocks) - mean(window)) *
        facts['block_size'],
        window_tokens=mean(window) * facts['block_size'],
        experts_hit_share=hit[0] / held[0])
    return 100.0 * need / records['peaks']['hbm_bytes_per_s'] / \
        (step_ms * 1e-3)
