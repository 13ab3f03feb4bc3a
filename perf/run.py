"""The benchmark's one command:

    python3 -m perf.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell once: set-up (weights from the seed, the
program's engine or train step built, every shape warmed), the
measured window, then the comparison with the plain reference. The
last line of standard output is the result as one JSON object.

Cells, configurations, traffic mixes and per-layer metrics are data
(BENCHMARK.json and the files under perf/): this file names none.

``--rehearse-cpu`` walks a cell end to end on the CPU at the
configuration's tiny rehearsal size. Its line names the device as
``cpu`` and carries no metric: a CPU run never gives a speed.
"""
import time

_T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def _parse(argv):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--rehearse-cpu', dest='rehearse',
                        action='store_true',
                        help='CPU walk-through at the tiny size; no '
                        'metric is printed')
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    # The cache the program sets (utils/jax_runtime) lies inside the
    # checkout at a fixed path unless JAX_COMPILATION_CACHE_DIR names
    # another; nothing else is written outside the checkout.
    from perf.lib import harness
    try:
        loaded = harness.load_cell(args.workload, args.rehearse)
        driver = harness.driver_for(loaded['config'])
        out = driver.run(loaded, args.seed, args.seconds,
                         bool(args.trace), args.rehearse,
                         _T_PROCESS_START)
    except harness.HarnessError as e:
        harness.fail(str(e))

    device = dict(out['device'])
    if args.rehearse:
        harness.say('rehearsal: platform: cpu - no metric is printed')
        print(harness.result_line(out['correct'], out['attempted'],
                                  out['failed'], {}, device))
        return 0
    # Each number compared beside its limit: the last lines of
    # standard error, and the last key of the result line.
    for c in out['compared']:
        print(harness.compared_line(c), file=sys.stderr, flush=True)
    device['memory_peak_bytes'] = out['memory_peak_bytes']
    breakdown = None
    if args.trace:
        from perf import trace_reduce
        from perf.lib import peaks
        trace = out['tracer'].load() if out['tracer'] else None
        records = {'registry': out['registry'], 'facts': out['facts'],
                   'model': out['model'], 'e2e': out['e2e'],
                   'peaks': peaks.peaks_for(device['kind']),
                   'perf_dir': loaded['perf_dir']}
        metrics = harness.read_layer_metrics(loaded, trace, records)
        if trace is not None:
            start, end = trace_reduce.window_of(trace)
            device['busy_s'] = trace_reduce.busy_seconds(trace)
            device['window_s'] = end - start
            breakdown = {
                'device_ops': trace_reduce.top_ops(trace),
                'idle_gaps': trace_reduce.idle_gaps(
                    trace, harness.ANNOTATION_PREFIX)}
    else:
        metrics = {m['name']: {'value': float(out['e2e'][m['name']]),
                               'unit': m['unit']}
                   for m in loaded['end_to_end']}
    print(harness.result_line(out['correct'], out['attempted'],
                              out['failed'], metrics, device,
                              breakdown, out['compared']), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
