"""``moe_busiest_over_mean``: the pairs the busiest held expert of a
layer and dispatch got, over the mean a held expert got - the
experts this replica holds x delta
``skytpu_batch_moe_busiest_expert_pairs_total`` / delta
``skytpu_batch_moe_held_pairs_total``. The count of experts is the
configuration file's own ``experts_held`` (16 of command-a's 128, all
64 of Xing4.0's, all 256 of JoyAI's), which the serving driver hands
over in ``records['facts']``: one entry covers cells that hold
different counts. None where the configuration states no
``experts_held`` or the program has no such counters."""
from perf.lib import readers


def reduce(trace, records):
    held = records['facts'].get('experts_held')
    if held is None:
        return None
    return readers.registry_counter_ratio(
        {'numerator': ['skytpu_batch_moe_busiest_expert_pairs_total'],
         'denominator': ['skytpu_batch_moe_held_pairs_total'],
         'scale': float(held)}, trace, records)
