"""What a serving mix offers under each seed, whatever the order: the
count of requests, the prompt and output tokens, a digest of the
multiset of (prompt length, output length, system prompt) and the
decode row-steps spent past a position. Every seed has to print the
same line; a count made on the CPU, never a speed.

    python3 -m perf.tools.offered --mix chat-steady --seeds 0,1,2,3,4,5 --past 3072
"""
import argparse
import json
import sys

from perf.lib import loadgen


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--mix', required=True)
    parser.add_argument('--seeds', default='0,1,2,3,4,5')
    parser.add_argument('--seconds', type=float, default=51.0)
    parser.add_argument('--past', type=int, default=3072)
    parser.add_argument('--vocab', type=int, default=32000)
    args = parser.parse_args(argv)
    spec = loadgen.load_traffic(args.mix)
    gen = loadgen.generator_for(spec['kind'])
    for seed in (int(s) for s in args.seeds.split(',')):
        requests = gen(spec, seed, args.seconds, args.vocab)
        print(json.dumps({'mix': args.mix, 'seed': seed,
                          'past': args.past,
                          **loadgen.offered(requests, args.past)}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
