"""Build the EXPERIMENTS.md §Paper-results + §Perf tables from artifacts.

Usage: PYTHONPATH=src python -m benchmarks.summarize
Writes experiments/summary.md (pasted into EXPERIMENTS.md).

``--diff-bench`` instead compares the serving telemetry time-series
(the ``timeseries`` blocks benchmarks/serving_load.py records into
BENCH_load.json / BENCH_chaos.json) against the previous committed
generation (``git show HEAD:<file>``): worst-window p99, peak queue
depth, and occupancy, flagging regressions past --tolerance.  Purely
informational on a noisy box — it prints REGRESSION markers but exits
zero unless --strict is given.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess

PAPER = 'experiments/paper'
DRY = 'experiments/dryrun/pod'


def _load(name):
    p = os.path.join(PAPER, name)
    if os.path.exists(p):
        with open(p) as f:
            return json.load(f)
    return None


def _cell(tagged):
    p = os.path.join(DRY, tagged + '.json')
    if not os.path.exists(p):
        return None
    with open(p) as f:
        r = json.load(f)
    from repro.kernels.tiling import DEVICE_PEAKS, MODELLED_KIND
    v5e = DEVICE_PEAKS[MODELLED_KIND]     # modelled, not measured
    coll = sum(r['collective_bytes'].values())
    return {'flops': r['flops_per_device'],
            'compute_s': r['flops_per_device'] / v5e['bf16_flops'],
            'bytes': r['bytes_per_device'],
            'args_gb': r['memory']['argument_bytes'] / 1e9,
            'mem_s': (2 * r['bytes_per_device']
                      + r['memory']['argument_bytes']) / v5e['hbm_bytes_per_s'],
            'coll_s': coll / v5e['ici_link_bytes_per_s']}


#: BENCH file -> scheduler-summary keys carrying a ``timeseries`` block
BENCH_TS = {
    'BENCH_load.json': ('static', 'compacting'),
    'BENCH_chaos.json': ('chaos_off', 'chaos_on', 'chaos_slo'),
    'BENCH_pipeline.json': ('single', 'pipeline', 'pipeline_static'),
}


def _ts_stats(block):
    """The three comparable scalars of one scheduler's timeseries block:
    (worst-window p99 s, peak queue depth, mean occupancy)."""
    ts = block.get('timeseries') or {}
    if not ts:
        return None
    p99 = (ts.get('worst_p99_window') or {}).get('p99_s')
    q = (ts.get('queue_depth') or {}).get('overall_peak')
    occ = [v for v in (ts.get('occupancy') or []) if v is not None]
    occ_mean = (sum(occ) / len(occ)) if occ else None
    return {'worst_p99_s': p99, 'peak_queue': q, 'mean_occupancy': occ_mean}


def diff_bench(tolerance=0.10, strict=False):
    """Diff current BENCH timeseries blocks vs the HEAD generation."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n_reg = 0
    for fname, keys in BENCH_TS.items():
        path = os.path.join(root, fname)
        if not os.path.exists(path):
            print(f'{fname}: not present, skipped')
            continue
        with open(path) as f:
            new = json.load(f)
        try:
            old = json.loads(subprocess.run(
                ['git', 'show', f'HEAD:{fname}'], cwd=root, check=True,
                capture_output=True, text=True).stdout)
        except (subprocess.CalledProcessError, json.JSONDecodeError):
            old = None
        print(f'{fname}:')
        for key in keys:
            cur = _ts_stats(new.get(key, {}))
            if cur is None:
                print(f'  {key}: no timeseries block in current run')
                continue
            prev = _ts_stats((old or {}).get(key, {}))
            if prev is None:
                print(f'  {key}: no previous-generation timeseries '
                      '(baseline recorded): '
                      + ' '.join(f'{k}={v}' for k, v in cur.items()))
                continue
            for metric, worse_is in (('worst_p99_s', 'higher'),
                                     ('peak_queue', 'higher'),
                                     ('mean_occupancy', 'lower')):
                a, b = prev[metric], cur[metric]
                if a is None or b is None or a == 0:
                    continue
                ratio = b / a
                regressed = (ratio > 1 + tolerance if worse_is == 'higher'
                             else ratio < 1 - tolerance)
                tag = '  REGRESSION' if regressed else ''
                n_reg += regressed
                print(f'  {key}.{metric}: {a:.6g} -> {b:.6g} '
                      f'({ratio:.2f}x){tag}')
    if n_reg:
        print(f'{n_reg} telemetry regression(s) past '
              f'{tolerance:.0%} tolerance')
        if strict:
            raise SystemExit(1)
    else:
        print('no telemetry regressions')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--diff-bench', action='store_true',
                    help='diff BENCH_*.json timeseries vs the HEAD '
                         'generation instead of building summary.md')
    ap.add_argument('--tolerance', type=float, default=0.10)
    ap.add_argument('--strict', action='store_true',
                    help='--diff-bench exits non-zero on regression')
    args = ap.parse_args()
    if args.diff_bench:
        return diff_bench(tolerance=args.tolerance, strict=args.strict)
    out = []
    pw = _load('pairwise_order.json')
    if pw:
        out.append('### Pairwise order experiments (Figs. 6-11)\n')
        out.append('| pair | winner | score A->B | score B->A |')
        out.append('|---|---|---|---|')
        # registry-generic: every 2-letter result entry is a pair; pairs
        # decided structurally (one order inapplicable) carry no scores
        for key, r in pw.items():
            if not (isinstance(r, dict) and len(key) == 2
                    and r.get('winner')):
                continue
            a, b = key
            sa, sb = (r.get('score_' + a + b), r.get('score_' + b + a))
            fmt = lambda s: f'{s:.4f}' if s is not None else 'structural'
            out.append(f"| {a}{b} | **{r['winner']}** "
                       f"| {fmt(sa)} | {fmt(sb)} |")
        out.append(f"\ntopological order: **{pw['topological_order']}**"
                   f" (theoretical: {pw.get('theoretical_order', '?')}, "
                   f"dropped weak edges: {pw.get('dropped_edges')})\n")
    sl = _load('sequence_law.json')
    if sl:
        out.append('### Sequence law (Table 1)\n')
        budgets = list(next(iter(sl['table'].values()))['budget_crs'])
        out.append('| sequence | ' + ' | '.join(budgets) + ' |')
        out.append('|---' * (len(budgets) + 1) + '|')
        for seq, row in sl['table'].items():
            cells = [f'{v:.0f}x' if v else '-'
                     for v in row['budget_crs'].values()]
            out.append(f'| {seq} | ' + ' | '.join(cells) + ' |')
        out.append(f"\nbaseline accuracy {sl['baseline_acc']:.3f}\n")
    for name, title in [('chain_cnn_archs.json',
                         'Full chain on CNN families (Tables 2-4)'),
                        ('chain_lm_archs.json',
                         'Full chain transferred to LMs (beyond paper)')]:
        ca = _load(name)
        if ca:
            out.append(f'### {title}\n')
            out.append('| model | baseline acc | final acc | BitOpsCR | CR |')
            out.append('|---|---|---|---|---|')
            for model, d in ca.items():
                if not (isinstance(d, dict) and 'history' in d):
                    continue                       # meta keys ('sequence')
                h0, h1 = d['history'][0], d['history'][-1]
                out.append(f"| {model} | {h0['acc']:.3f} | {h1['acc']:.3f} "
                           f"| {h1['BitOpsCR']:.0f}x | {h1['CR']:.1f}x |")
            out.append('')
    rp = _load('repeat_compression.json')
    if rp:
        out.append('### Repeating compression (Fig. 14)\n')
        out.append('| variant | acc | BitOpsCR |')
        out.append('|---|---|---|')
        for k, v in rp.items():
            out.append(f"| {k} | {v['acc']:.3f} | {v['BitOpsCR']:.1f}x |")
        out.append('')

    out.append('### §Perf cells (final, consistent measurement)\n')
    rows = [
        ('mixtral train_4k baseline', 'mixtral-8x7b__train_4k_base3'),
        ('mixtral train_4k EP', 'mixtral-8x7b__train_4k'),
        ('deepseek train_4k baseline', 'deepseek-v3-671b__train_4k_base3'),
        ('deepseek train_4k EP(a2a)', 'deepseek-v3-671b__train_4k'),
        ('qwen2 decode_32k baseline', 'qwen2-72b__decode_32k'),
        ('qwen2 decode_32k int8-KV', 'qwen2-72b__decode_32k_opt7_kv8'),
    ]
    out.append('| cell | compute s | memory s | collective s | args GB |')
    out.append('|---|---|---|---|---|')
    for label, tag in rows:
        c = _cell(tag)
        if c:
            out.append(f"| {label} | {c['compute_s']:.3f} | {c['mem_s']:.3f}"
                       f" | {c['coll_s']:.3f} | {c['args_gb']:.2f} |")
    text = '\n'.join(out) + '\n'
    with open('experiments/summary.md', 'w') as f:
        f.write(text)
    print(text)


if __name__ == '__main__':
    main()
