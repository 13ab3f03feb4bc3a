"""The generic readers of per-layer metrics. A metric's
``layer_metrics/<name>.json`` names one of these and gives its
parameters; a reader that finds nothing to read returns None, and the
harness leaves the metric out of the line. Every reader is called as
``reader(params, trace, records)``:

  ``trace``     the loaded profiler trace (perf/trace_reduce.load), or
                None where the run recorded none
  ``records``   what else a traced run offers:
    ``registry``  RegistryWindow over the measured window, or None
    ``facts``     what the driver states about the run (batch, seq,
                  chips, per-request times, ...)
    ``model``     the configuration's published keys
    ``e2e``       the end-to-end values of this same run
    ``peaks``     the chip's peaks (perf/peaks.json)
    ``perf_dir``  the benchmark's directory in this checkout
"""
import re
from typing import Any, Dict, Optional

from perf import costs
from perf import trace_reduce
from perf.costs import model as model_costs
from perf.lib import stats


def registry_counter_ratio(params: Dict[str, Any], trace, records: Dict[str, Any]
                           ) -> Optional[float]:
    """``scale`` x sum of deltas of ``numerator`` families over sum of
    deltas of ``denominator`` families."""
    reg = records.get('registry')
    if reg is None:
        return None
    def total(names):
        deltas = [reg.delta(n) for n in names]
        if any(d is None for d in deltas):
            return None
        return sum(d[0] for d in deltas)
    num, den = total(params['numerator']), total(params['denominator'])
    if num is None or not den:
        return None
    return params.get('scale', 1.0) * num / den


def registry_hist_mean(params: Dict[str, Any], trace, records: Dict[str, Any]
                       ) -> Optional[float]:
    """``scale`` x (sum delta / count delta) of one histogram."""
    reg = records.get('registry')
    delta = reg.delta(params['family']) if reg is not None else None
    if delta is None or not delta[1]:
        return None
    return params.get('scale', 1.0) * delta[0] / delta[1]


def gauge_sampled(params: Dict[str, Any], trace, records: Dict[str, Any]
                  ) -> Optional[float]:
    """``stat`` (mean or peak) of a gauge's samples over the window,
    optionally over the last value of gauge ``over``, x ``scale``."""
    reg = records.get('registry')
    samples = reg.samples.get(params['gauge']) if reg is not None \
        else None
    if not samples:
        return None
    value = max(samples) if params.get('stat') == 'peak' \
        else sum(samples) / len(samples)
    if 'over' in params:
        base = reg.last(params['over'])
        if not base:
            return None
        value /= base
    return params.get('scale', 1.0) * value


def fact_percentile(params: Dict[str, Any], trace, records: Dict[str, Any]
                    ) -> Optional[float]:
    """Ceil-based percentile ``pct`` of a list of values the driver
    states as a fact of the run (``fact``)."""
    values = records['facts'].get(params['fact'])
    if not values:
        return None
    return stats.percentile(values, params['pct'])


def fact_mean(params: Dict[str, Any], trace, records: Dict[str, Any]
              ) -> Optional[float]:
    """Mean of a list of values the driver states as a fact of the
    run (``fact``): steadier than a median where the values fall
    into two groups."""
    values = records['facts'].get(params['fact'])
    if not values:
        return None
    return sum(values) / len(values)


def _module(params: Dict[str, Any], trace):
    if trace is None:
        return None
    rx = re.compile(params['module'])
    calls, seconds = 0, 0.0
    for name, rec in trace_reduce.module_times(trace).items():
        if rx.search(name):
            calls += rec['calls']
            seconds += rec['seconds']
    return (calls, seconds) if calls else None


def xla_module_ms(params: Dict[str, Any], trace, records: Dict[str, Any]
                  ) -> Optional[float]:
    """Device milliseconds per call of the compiled programs whose
    name matches ``module``, over ``per`` (a fact: steps to a call)."""
    found = _module(params, trace)
    if found is None:
        return None
    calls, seconds = found
    per = records['facts'][params['per']] if 'per' in params else 1
    return seconds / calls / per * 1e3


def kernel_roofline(params: Dict[str, Any], trace, records: Dict[str, Any]
                    ) -> Optional[float]:
    """Share (%) of its roofline a kernel reached: the least time the
    chip could take for the work (``work``: a function under
    perf/costs/, ``<file>.<function>``, giving one layer's operations and bytes; larger of operations
    over peak and bytes over bandwidth) times the layers and steps
    traced, over the kernel's summed device time."""
    if trace is None:
        return None
    hit = trace_reduce.op_seconds(trace, params['kernels'])
    steps = _module({'module': params['step_module']}, trace)
    if not hit['calls'] or steps is None:
        return None
    facts = records['facts']
    work = costs.cost_function(params['work'], records.get('perf_dir'))(
        records['model'], facts['batch_per_chip'], facts['seq'])
    least = max(work['flops'] / records['peaks']['bf16_flops_per_s'],
                work['bytes'] / records['peaks']['hbm_bytes_per_s'])
    layers = records['model']['num_hidden_layers']
    return 100.0 * least * layers * steps[0] / hit['seconds']


def kernel_share(params: Dict[str, Any], trace, records: Dict[str, Any]
                 ) -> Optional[float]:
    """Share (%) of the device time of the compiled programs matching
    ``step_module`` that the operations matching ``kernels`` took."""
    del records
    if trace is None:
        return None
    hit = trace_reduce.op_seconds(trace, params['kernels'])
    steps = _module({'module': params['step_module']}, trace)
    if not hit['calls'] or steps is None:
        return None
    return 100.0 * hit['seconds'] / steps[1]


def collective_exposed(params: Dict[str, Any], trace, records: Dict[str, Any]
                       ) -> Optional[float]:
    """Share (%) of the worst device's busy time in which a
    collective runs and no compute does."""
    del params
    if trace is None:
        return None
    found = trace_reduce.collective_exposed(trace)
    if not found['collective_s'] or not found['busy_s']:
        return None
    return 100.0 * found['exposed_s'] / found['busy_s']


def model_flops_utilization(params: Dict[str, Any], trace, records: Dict[str, Any]
                            ) -> Optional[float]:
    """End-to-end utilisation (%): this run's tokens per second per
    chip x the benchmark's own operations per token over the chip's
    peak. Recomputed operations are not counted."""
    rate = records['e2e'].get(params['rate'])
    if rate is None:
        return None
    per_token = model_costs.train_flops_per_token(
        records['model'], records['facts']['seq'],
        frozen_base=records['facts']['frozen_base'])
    return 100.0 * rate * per_token / records['peaks']['bf16_flops_per_s']
