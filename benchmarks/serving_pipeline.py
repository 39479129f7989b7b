"""Pipeline-parallel serving benchmark: one device vs a placed pipeline.

Packs the exported CNN's stages onto multiple real jax devices with the
greedy-LPT cost solver (``repro/serving/placement.py``) and A/Bs three
schedulers on the SAME Poisson trace and the SAME measured per-stage
costs:

* ``single``          — the single-device continuous-batching scheduler
  (every segment serialized through one executor: the pipeline's lower
  bound is this run's makespan).
* ``pipeline``        — :class:`PipelineParallelScheduler`, compacting:
  stage *k* runs on its placed device, the int8 carry streams between
  devices (``transfer.carry``), survivors from any cohort backfill.
* ``pipeline_static`` — same placement, ``compact=False``: cohorts ride
  intact, exited slots stay empty (what compaction buys in device time).

Methodology matches serving_load.py: median per-stage costs at the fixed
slot geometry drive a simulated event clock while the data path executes
for real — on N accelerator devices, or on N **forced host devices**
when the caller set ``JAX_PLATFORMS=cpu`` (the benchmark then sets
``--xla_force_host_platform_device_count=N`` before jax initializes; with
fewer accelerator devices than requested it fails).  Every sampled request's
answer is checked bit-exact against the monolithic ``fn_exits`` serving
it alone at the same geometry, and the three schedulers must agree
answer-for-answer: placement moves WHERE stages run, never what they
compute.

Results go to BENCH_pipeline.json: the placement (assignment, loads,
LPT bound, balance), single vs pipeline makespan and the speedup, and
per-scheduler latency/throughput summaries with windowed ``timeseries``
blocks plus per-device ``device_occupancy`` series for the pipeline runs
(``summarize.py --diff-bench`` tracks them across generations).
``--smoke`` is the CI wiring: tiny trace, asserts drain + bit-exactness
+ strict trace invariants on the recorded pipeline spans, writes nothing
unless --out is given.

    PYTHONPATH=src python benchmarks/serving_pipeline.py [--devices 8]
    PYTHONPATH=src python benchmarks/serving_pipeline.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def ensure_devices(n: int) -> None:
    """Make this process see ``n`` devices, or fail.

    One process per chip: nothing here re-execs a child after touching
    jax.  Forced host devices are used only when the caller already chose
    the CPU (``JAX_PLATFORMS=cpu``); they are set in-process, before the
    backend initializes.  On an accelerator, fewer than ``n`` devices is an
    error, never a silent fall back to the CPU."""
    if os.environ.get('JAX_PLATFORMS') == 'cpu':
        flags = [f for f in os.environ.get('XLA_FLAGS', '').split()
                 if not f.startswith('--xla_force_host_platform_device_count')]
        flags.append(f'--xla_force_host_platform_device_count={n}')
        os.environ['XLA_FLAGS'] = ' '.join(flags)
    import jax
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f'{len(devs)} {devs[0].platform} device(s) < --devices {n}; '
            f'run with JAX_PLATFORMS=cpu for forced host devices')


def makespan(completions) -> float:
    """Arrival of the first request -> completion of the last."""
    return (max(c.t_done for c in completions.values())
            - min(c.t_arrival for c in completions.values()))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', default='resnet8-cifar')
    ap.add_argument('--slots', type=int, default=32)
    ap.add_argument('--requests', type=int, default=256)
    ap.add_argument('--iters', type=int, default=10)
    ap.add_argument('--devices', type=int, default=8,
                    help='device count; forced host devices under '
                         'JAX_PLATFORMS=cpu, else a minimum')
    ap.add_argument('--rate', type=float, default=None,
                    help='arrival rate (req/s); default 2x the single-'
                         'device full-depth capacity, so the pipeline '
                         'win shows up as makespan, not idle time')
    ap.add_argument('--threshold', type=float, default=None)
    ap.add_argument('--quantile', type=float, default=0.5)
    ap.add_argument('--pallas', action='store_true')
    ap.add_argument('--transfer-frac', type=float, default=0.02,
                    help='carry-transfer charge as a fraction of the '
                         'consuming stage cost')
    ap.add_argument('--seed', type=int, default=0,
                    help='placement tie-break seed')
    ap.add_argument('--oracle-all', action='store_true')
    ap.add_argument('--trace', default=None, metavar='OUT.json',
                    help='write the pipeline run as validated '
                         'Chrome-trace JSON')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny CI run: 24 requests, 8 slots, 2 iters, '
                         'full oracle, strict trace check, no file '
                         'output unless --out is given')
    ap.add_argument('--out', default=None)
    args = ap.parse_args()
    if args.smoke:
        args.slots, args.requests, args.iters = 8, 24, 2
    ensure_devices(args.devices)

    import jax
    import numpy as np

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from serving_load import (check_oracle, measure_stage_costs,
                              poisson_trace, validate_and_write_trace)
    from repro.configs.cnn import CNN_REGISTRY
    from repro.core.export import calibrate_exit_threshold, export_cnn
    from repro.core.family import CNNFamily
    from repro.data import SyntheticImages
    from repro.kernels.tiling import batch_slots
    from repro.obs import Tracer, check_trace
    from repro.serving import (ContinuousBatchScheduler,
                               PipelineParallelScheduler)

    out = args.out
    if out is None and not args.smoke:
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'BENCH_pipeline.json')

    use_pallas = args.pallas or jax.default_backend() == 'tpu'
    slots = batch_slots(args.slots)
    fam = CNNFamily(SyntheticImages())
    cfg = CNN_REGISTRY[args.config].replace(w_bits=8, a_bits=8)
    params = fam.init(jax.random.key(0), cfg)
    params, cfg = fam.add_exits(jax.random.key(1), params,
                                cfg.replace(exit_stages=()),
                                fam.default_exit_points(cfg))
    cfg = cfg.replace(w_bits=8, a_bits=8)

    key = jax.random.key(7)
    xs = jax.random.normal(key, (args.requests, 32, 32, 3))
    calib = jax.random.normal(jax.random.fold_in(key, 1),
                              (slots, 32, 32, 3))
    model = export_cnn(params, cfg, use_pallas=use_pallas, calibrate=calib)
    threshold = args.threshold
    if threshold is None:
        threshold = calibrate_exit_threshold(model, calib,
                                             quantile=args.quantile)
        print(f'calibrated exit threshold: {threshold:.4f} '
              f'(target exit quantile {args.quantile})')

    stage_costs_us, mono_us = measure_stage_costs(
        model, calib, iters=args.iters)
    costs = [c * 1e-6 for c in stage_costs_us]

    # oversubscribe the single device's full-depth capacity so both
    # schedulers run queue-saturated and the A/B measures service rate
    rate = args.rate or 2.0 * slots / sum(costs)
    trace = poisson_trace(xs, rate, seed=0)

    single = ContinuousBatchScheduler(model, slots=slots,
                                      threshold=threshold,
                                      stage_costs=costs)
    s_comp, s_met = single.run_trace(trace)

    tracer = Tracer()
    pipe = PipelineParallelScheduler(
        model, slots=slots, threshold=threshold, stage_costs=costs,
        transfer_frac=args.transfer_frac, seed=args.seed, tracer=tracer)
    p_comp, p_met = pipe.run_trace(trace)
    placement = pipe.placement

    stat = PipelineParallelScheduler(
        model, slots=slots, threshold=threshold, stage_costs=costs,
        compact=False, transfer_frac=args.transfer_frac, seed=args.seed)
    t_comp, t_met = stat.run_trace(trace)

    runs = (('single', s_comp), ('pipeline', p_comp),
            ('pipeline_static', t_comp))
    for name, comp in runs:
        assert len(comp) == args.requests, \
            f'{name}: drained {len(comp)}/{args.requests}'
    oracle_reqs = (trace if (args.smoke or args.oracle_all)
                   else trace[:: max(1, len(trace) // 16)])
    for name, comp in runs:
        bad = check_oracle(model, comp, oracle_reqs, threshold, slots)
        assert not bad, f'{name}: requests {bad[:8]} diverge from oracle'
    for name, comp in runs[1:]:
        assert all(comp[r.rid].exit_stage == s_comp[r.rid].exit_stage
                   and np.array_equal(comp[r.rid].logits,
                                      s_comp[r.rid].logits)
                   for r in trace), f'{name} disagrees with single-device'

    check_trace(tracer, p_comp, strict=True)
    if args.trace:
        validate_and_write_trace(tracer, p_comp, args.trace)
    n_transfer = sum(1 for s in tracer.spans if s.name == 'transfer.carry')

    mk = {name: makespan(comp) for name, comp in runs}
    speedup = mk['single'] / max(mk['pipeline'], 1e-12)
    sums = {}
    for name, met in (('single', s_met), ('pipeline', p_met),
                      ('pipeline_static', t_met)):
        block = met.summary()
        block['makespan_s'] = round(mk[name], 6)
        block['timeseries'] = met.timeseries()
        occ = met.device_occupancy()
        if occ:
            block['device_occupancy'] = occ
        sums[name] = block

    results = {
        'backend': jax.default_backend(),
        'int8_path': 'pallas' if use_pallas else 'jnp-ref',
        'config': cfg.name,
        'n_devices': len(jax.devices()),
        'batch_geometry': {'slots_requested': args.slots,
                           'slots_padded': slots,
                           'image': [32, 32, 3]},
        'n_requests': args.requests,
        'arrival_rate_rps': round(rate, 3),
        'exit_threshold': round(threshold, 6),
        'transfer_frac': args.transfer_frac,
        'timing': {'iters': args.iters, 'reduction': 'median',
                   'stage_costs_us': [round(c, 1) for c in stage_costs_us],
                   'monolithic_us': round(mono_us, 1)},
        'placement': placement.summary(),
        'transfer_spans': n_transfer,
        'single': sums['single'],
        'pipeline': sums['pipeline'],
        'pipeline_static': sums['pipeline_static'],
        'pipeline_speedup_x': round(speedup, 3),
        'pipeline_vs_static_x': round(
            mk['pipeline_static'] / max(mk['pipeline'], 1e-12), 3),
    }
    print(f"{cfg.name} slots={slots} rate={rate:.0f}/s "
          f"devices={len(jax.devices())}")
    print(f"  placement: {placement.summary()['assignment']} "
          f"loads={placement.summary()['loads']} "
          f"balance={placement.balance:.3f} "
          f"(LPT bound {placement.bound * 1e3:.3f}ms)")
    for name, _ in runs:
        b = sums[name]
        print(f"  {name + ':':17s}makespan={b['makespan_s'] * 1e3:.2f}ms "
              f"p99={b['p99_latency_s'] * 1e3:.2f}ms "
              f"throughput={b['throughput_rps']:.0f} req/s")
    print(f"  pipeline speedup: {speedup:.2f}x vs single "
          f"({results['pipeline_vs_static_x']:.2f}x vs static cohorts); "
          f"{n_transfer} carry transfers")
    occ = sums['pipeline'].get('device_occupancy', {})
    for d in sorted(occ, key=int):
        bar = ''.join('#' if v > 0.5 else ('+' if v > 0 else '.')
                      for v in occ[d])
        print(f"    device{d} [{bar}]")
    if args.smoke:
        print('pipeline smoke OK: drained, bit-exact vs single-device '
              'and oracle, trace invariants hold')
    if out:
        with open(out, 'w') as f:
            json.dump(results, f, indent=1)
        print(f'wrote {out}')


if __name__ == '__main__':
    main()
