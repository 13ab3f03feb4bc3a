"""``looped_decode_hbm_roofline``: a decode step's share (%) of the
bound that memory bandwidth sets, for a looped layer stack - the bytes
one step must read (perf/costs/looped_decode_step.py: the layers'
weights once a pass, the head, the valid keys and values of the active
rows over every pass's and layer's KV entry, scales, embedding rows)
over the chip's bandwidth, over the device time of a decode step.
Decode is one unit, as in ``decode_hbm_roofline``. None where the
configuration has no loop or the trace no decode program."""
from perf.costs import looped_decode_step
from perf.lib import readers


def reduce(trace, records):
    if 'total_ut_steps' not in records['model']:
        return None
    step_ms = readers.xla_module_ms(
        {'module': '^jit_decode_steps_paged$',
         'per': 'steps_per_dispatch'}, trace, records)
    reg = records.get('registry')
    if step_ms is None or reg is None:
        return None
    rows = reg.samples.get('skytpu_batch_slots_occupied')
    blocks = reg.samples.get('skytpu_batch_kv_blocks_used')
    if not rows or not blocks:
        return None
    facts = records['facts']
    need = looped_decode_step.looped_decode_step_bytes(
        records['model'], facts['weight_bytes'], facts['kv_bytes'],
        rows=sum(rows) / len(rows),
        kv_tokens=sum(blocks) / len(blocks) * facts['block_size'])
    return 100.0 * need / records['peaks']['hbm_bytes_per_s'] / \
        (step_ms * 1e-3)
