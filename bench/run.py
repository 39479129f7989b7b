"""Benchmark command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the repository root on a machine with the chips the cell asks
for.  The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit);
the last lines of standard error repeat the checks.  Without a TPU, or
with fewer chips than the cell needs, or without the program beside it,
it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(ROOT, 'src'))
    import harness
    try:
        import repro.serving  # noqa: F401
    except ImportError as e:
        harness.log(f'FAIL: the program is not beside the benchmark ({e})')
        return 2
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        harness.log(f'FAIL: {e}')
        return 3
    print(json.dumps(result), flush=True)
    for name, c in result['checks'].items():
        harness.log(f'check {name}={c["value"]} limit={c["limit"]}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
