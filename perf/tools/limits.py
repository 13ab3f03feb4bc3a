"""Read, on the chip and at a cell's own size, what the limits of
``correct`` are set from: the control's numbers (the reference at the
nearest precision below the stated one) on a few seeds, beside the
program's own where the control's run produces them. The program's
dozen sound seeds are the benchmark's own runs, each of which prints
every number compared.

    python3 -m perf.tools.limits --workload <cell> --seeds 1,2,3 --seconds 51

A serving cell compares the last ``check_tokens`` tokens of requests
that finished inside the window with all of those tokens inside it
(``serve_engine.check_sample``), so the window has to be long enough
for ``check_requests`` such requests: the cell's own length is, and
``served_tokens_missing`` in the ``sound`` readings says whether a
shorter one was.
"""
import argparse
import gc
import json
import os
import sys

from perf.lib import harness


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--seconds', type=float, default=15.0)
    parser.add_argument('--rehearse-cpu', dest='rehearse',
                        action='store_true')
    args = parser.parse_args(argv)
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    loaded = harness.load_cell(args.workload, args.rehearse)
    driver = harness.driver_for(loaded['config'])
    for seed in (int(s) for s in args.seeds.split(',')):
        got = driver.control_readings(loaded, seed, args.seconds,
                                      args.rehearse)
        harness.say('limits ' + json.dumps(
            {'workload': args.workload, 'seed': seed, **got}))
        gc.collect()
    return 0


if __name__ == '__main__':
    sys.exit(main())
