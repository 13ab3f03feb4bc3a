"""``decode_hbm_roofline``: a decode step's share (%) of the bound
that memory bandwidth sets - the bytes one step must read
(perf/costs/decode_step.py: matmul weights and head with scales, the
valid keys and values of the active rows, their embedding rows) over
the chip's bandwidth, over the device time of a decode step. Decode
is one unit here until the tracing issue names its parts. Bandwidth
binds: a step at 24 rows does 2 x 7.1 G x 24 operations (0.9 ms of
the chip's int8 peak) against 7.4 GB and more to read (9 ms)."""
from perf.costs import decode_step
from perf.lib import readers


def reduce(trace, records):
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
    need = decode_step.decode_step_bytes(
        records['model'], facts['weight_bytes'], facts['kv_bytes'],
        rows=sum(rows) / len(rows),
        kv_tokens=sum(blocks) / len(blocks) * facts['block_size'])
    return 100.0 * need / records['peaks']['hbm_bytes_per_s'] / \
        (step_ms * 1e-3)
