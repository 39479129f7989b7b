"""Readings that the limits in ``bench/limits/`` are set from, on the chip.

    python3 bench/probe_limits.py --config resnet34-cifar --seeds 12 --control-seeds 3
    python3 bench/probe_limits.py --cells vgg19-exit-backlog --seeds 3 --seed0 7100100003

For every cell of the configuration, or each cell of ``--cells``, in one
process (which holds the chip): the program as the configuration states
it (w8/a8) on ``--seeds`` seeds, and the control, the program's own int4
path (w4/a4, the nearest precision below int8), on the first
``--control-seeds`` of those seeds, each a full set-up
and a short window at the cell's own load, then the same check as a
benchmark run.  The lower reading of ``logit_rel_err`` is the largest the
program gives, the upper the smallest the control gives.  Prints one JSON
line per run and a summary line.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# seeds of their own, above 32 bits
SEED0 = 7_100_000_001


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--config', help='every cell of this configuration')
    ap.add_argument('--cells', nargs='+', help='these cells')
    ap.add_argument('--seeds', type=int, default=12)
    ap.add_argument('--seed0', type=int, default=SEED0,
                    help='first seed; the others follow 7919 apart')
    ap.add_argument('--control-seeds', type=int, default=3)
    ap.add_argument('--seconds', type=float, default=1.0,
                    help='window of a backlog run (one chunk is enough)')
    ap.add_argument('--closed-seconds', type=float, default=8.0,
                    help='window of a closed-loop run: long enough to '
                         'answer as many requests as a run compares')
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.join(ROOT, 'src'))
    import harness

    if bool(args.config) == bool(args.cells):
        ap.error('give --config or --cells')
    cells = args.cells or [w['name'] for w in harness.load_json(
        harness.MANIFEST)['workloads'] if w['config'] == args.config]
    seconds = {c: args.closed_seconds
               if harness.load_cell(c)['traffic']['mode'] == 'closed'
               else args.seconds for c in cells}
    runs = []
    for i in range(args.seeds):
        seed = args.seed0 + 7919 * i
        for bits in ([8, 4] if i < args.control_seeds else [8]):
            for cell in cells:
                diag = {}
                r = harness.run(cell, seed, seconds[cell], False,
                                t_start=time.perf_counter(), bits=bits,
                                diag=diag)
                errs = diag['rel_errs']
                row = {'cell': cell, 'seed': seed, 'bits': bits,
                       'correct': r['correct'],
                       'logit_rel_err': r['checks']['logit_rel_err']['value'],
                       'median_rel_err': float(sorted(errs)[len(errs) // 2]),
                       'oracle_mismatch':
                           r['checks']['oracle_mismatch']['value'],
                       'unanswered': r['checks']['unanswered']['value'],
                       'exit_mix': {str(k): v for k, v in
                                    sorted(diag['exit_mix'].items())},
                       'threshold': diag['threshold']}
                runs.append(row)
                print('PROBE ' + json.dumps(row), flush=True)
    summary = {}
    for cell in cells:
        mine = [r for r in runs if r['cell'] == cell]
        sound = [r['logit_rel_err'] for r in mine if r['bits'] == 8]
        ctrl = [r['logit_rel_err'] for r in mine if r['bits'] == 4]
        summary[cell] = {
            'lower': max(sound), 'sound_runs': len(sound),
            'sound_readings': sound,
            'upper': min(ctrl) if ctrl else None, 'control_runs': len(ctrl),
            'control_readings': ctrl,
            'oracle_mismatch_max': max(r['oracle_mismatch'] for r in mine
                                       if r['bits'] == 8)}
    print('SUMMARY ' + json.dumps(summary), flush=True)


if __name__ == '__main__':
    main()
