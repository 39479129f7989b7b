"""CNN serving launcher: export a compressed CNN and serve batched traffic.

Runs the short chain (or skips straight to export with --no-train), compiles
the result to the int8 serving path (core/export.py), and drives a batched
early-exit serving loop over a synthetic eval stream, reporting throughput
and the per-stage exit distribution — the deployed realization of the
paper's D→P→Q→E chain.

    PYTHONPATH=src python -m repro.launch.serve_cnn --config resnet8-cifar \
        --batches 8 --batch 64 --threshold 0.85

``--server`` switches from caller-assembled static batches to the request
runtime (repro/serving/): requests arrive on a Poisson trace, the
continuous-batching scheduler forms tile-padded batches, returns
early-exited samples after their stage segment, compacts the survivors,
and backfills freed slots from the queue; the run reports p50/p99 latency,
throughput, exit mix, and batch occupancy.

    PYTHONPATH=src python -m repro.launch.serve_cnn --server \
        --requests 256 --rate 800 --slots 32

``--deadline-ms`` attaches per-request deadlines and turns on the SLO
layer (deadline admission + graceful degradation through the exit heads;
no admitted request finishes late).  ``--chaos`` serves the trace on the
replica pool under a seeded fault plan (replica kill mid-batch, straggler
slowdown) and reports availability/failover/straggler counters.  Both run
on a simulated clock built from locally measured stage costs.

    PYTHONPATH=src python -m repro.launch.serve_cnn --server \
        --requests 128 --deadline-ms 40 --chaos --replicas 2

``--pipeline`` serves the trace pipeline-parallel across every visible
jax device: the placement solver packs stage *k* onto a device by
measured cost (greedy LPT, the reported load-balance bound), the int8
carry streams between devices, and the run prints the placement next to
the usual latency numbers.  Force a device count with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the count is
locked at backend init).  ``--chaos`` composes: a seeded device kill
mid-trace, survivors re-solved.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve_cnn --server \
        --pipeline --requests 256 --rate 800 --slots 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _measure_stage_costs(model, x, iters=5):
    """Median per-segment batch cost (seconds) at the geometry of ``x`` —
    the simulated clock for --deadline-ms / --chaos runs."""
    costs, carry = [], x
    for k in range(model.n_stages):
        fn = model.stage_fns[k]
        jax.block_until_ready(fn(model.params, carry))   # compile off-clock
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(model.params, carry))
            ts.append(time.perf_counter() - t0)
        costs.append(float(np.median(ts)))
        if k < model.n_stages - 1:
            _, carry = model.run_stage(k, carry)
    return costs


def _serve_trace(model, fam, cfg, args, tracer=None):
    """--server mode: drive the request scheduler over a Poisson trace on
    the wall clock (cf. benchmarks/serving_load.py for the median-cost
    simulated A/B against static batching).  --deadline-ms adds the SLO
    layer and --chaos runs the replica pool under a seeded fault plan —
    both on the simulated clock built from locally measured stage costs."""
    from repro.core.export import calibrate_exit_threshold
    from repro.serving import (ChaosPlan, ContinuousBatchScheduler,
                               ReplicaPoolScheduler, Request, SLOPolicy)

    rng = np.random.default_rng(0)
    stream = fam.eval_batches(-(-args.requests // args.batch), args.batch)
    xs = jnp.concatenate([x for x, _ in stream])[:args.requests]
    ys = jnp.concatenate([y for x, y in stream])[:args.requests]
    threshold = args.threshold
    if threshold is None:
        threshold = calibrate_exit_threshold(model, xs[:args.slots])
        print(f'calibrated exit threshold: {threshold:.4f}')
    t = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    deadlines = [None] * args.requests
    if args.deadline_ms is not None:
        deadlines = [float(ti) + args.deadline_ms * 1e-3 for ti in t]
    reqs = [Request(i, xs[i], float(t[i]), deadline=deadlines[i])
            for i in range(args.requests)]
    simulated = args.chaos or args.deadline_ms is not None or args.pipeline
    if simulated:
        # the SLO layer and the replica pool need a deterministic clock:
        # measure per-segment batch costs locally and simulate on them
        costs = _measure_stage_costs(model, xs[:args.slots])
        print('measured stage costs: '
              + ' '.join(f'{c * 1e3:.2f}ms' for c in costs))
        slo = SLOPolicy(stage_costs=costs) \
            if args.deadline_ms is not None else None
        if args.pipeline:
            from repro.serving import PipelineParallelScheduler
            plan = None
            if args.chaos:
                horizon = max(float(t[-1]),
                              args.requests / args.slots * sum(costs))
                plan = ChaosPlan.seeded(args.chaos_seed,
                                        len(jax.devices()), horizon)
            sched = PipelineParallelScheduler(
                model, slots=args.slots, threshold=threshold,
                stage_costs=costs, max_wait=args.max_wait, chaos=plan,
                tracer=tracer)
            p = sched.placement.summary()
            print(f"placement over {p['n_devices']} devices: "
                  f"{p['assignment']} loads={p['loads']} "
                  f"balance={p['balance']} (LPT bound {p['bound']})")
        elif args.chaos:
            horizon = max(float(t[-1]),
                          args.requests / args.slots * sum(costs)
                          / args.replicas)
            plan = ChaosPlan.seeded(args.chaos_seed, args.replicas, horizon)
            sched = ReplicaPoolScheduler(
                model, slots=args.slots, threshold=threshold,
                stage_costs=costs, slo=slo, replicas=args.replicas,
                min_replicas=args.replicas, max_replicas=args.max_replicas,
                restore=lambda: model, restore_delay=costs[0], chaos=plan,
                tracer=tracer)
        else:
            sched = ContinuousBatchScheduler(
                model, slots=args.slots, threshold=threshold,
                stage_costs=costs, max_wait=args.max_wait, slo=slo,
                tracer=tracer)
    else:
        sched = ContinuousBatchScheduler(
            model, slots=args.slots, threshold=threshold,
            max_wait=args.max_wait, tracer=tracer)
    # warm EVERY stage program off the clock: threshold 2.0 means nothing
    # exits, so the warm batch traverses all segments (a real-threshold
    # warm-up could exit at head 1 and leave deeper segments uncompiled,
    # charging their jit to the first unlucky real batch's latency)
    ContinuousBatchScheduler(
        model, slots=args.slots, threshold=2.0).run_trace(
            [Request(-1 - i, xs[i], 0.0)
             for i in range(min(4, args.requests))])
    completions, metrics = sched.run_trace(reqs)
    s = metrics.summary()
    hit = sum(1 for i, c in completions.items() if c.pred == int(ys[i]))
    print(f'config={cfg.name} backend={jax.default_backend()} '
          f'slots={sched.slots} threshold={threshold:.3f}'
          + (' clock=simulated' if simulated else ''))
    print(f"served {s['n_requests']} requests at rate={args.rate:.0f}/s: "
          f"throughput={s['throughput_rps']:.0f} req/s "
          f"p50={s['p50_latency_s'] * 1e3:.2f}ms "
          f"p99={s['p99_latency_s'] * 1e3:.2f}ms "
          f"acc={hit / max(len(completions), 1):.3f}")
    print(f"  exit mix: {s['exit_mix']}  "
          f"occupancy: {s['batch_occupancy']}")
    print(f"  latency split: queue-wait p50={s['p50_queue_wait_s'] * 1e3:.2f}"
          f"ms p99={s['p99_queue_wait_s'] * 1e3:.2f}ms | execute "
          f"p50={s['p50_execute_s'] * 1e3:.2f}ms "
          f"p99={s['p99_execute_s'] * 1e3:.2f}ms")
    if 'slo' in s:
        slo_s = s['slo']
        print(f"  SLO deadline={args.deadline_ms:.1f}ms: "
              f"attainment={slo_s['attainment']:.3f} "
              f"late={slo_s['n_late']} rejected={s['n_rejected']} "
              f"degraded={s['n_degraded']} "
              f"(mix {s['degraded_exit_mix']})")
        assert slo_s['n_late'] == 0, 'never-late contract violated'
    if 'resilience' in s:
        r = s['resilience']
        print(f"  chaos: availability={s['availability']:.4f} "
              f"kills={r['kills']} failovers={r['failovers']} "
              f"straggler_flags={r['straggler_flags']} "
              f"evictions={r['evictions']} "
              f"peak_replicas={r['peak_replicas']}")
    print('  ' + metrics.telemetry_digest())
    if tracer is not None:
        from repro.obs import check_trace
        check_trace(tracer, completions, strict=True)
        tracer.write(args.trace)
        print(f'  trace: {len(tracer.spans)} spans -> {args.trace} '
              f'(open at https://ui.perfetto.dev)')


def main():
    from repro.configs.cnn import CNN_REGISTRY
    from repro.core.export import export_cnn
    from repro.core.family import CNNFamily
    from repro.core.passes import Trainer
    from repro.data import SyntheticImages
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', default='resnet8-cifar',
                    choices=sorted(CNN_REGISTRY))
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--batches', type=int, default=8)
    ap.add_argument('--threshold', type=float, default=None,
                    help='exit threshold (default 0.85; --server default '
                         'calibrates on the stream)')
    ap.add_argument('--steps', type=int, default=60,
                    help='QAT fine-tune steps before export (0 = raw init)')
    ap.add_argument('--pallas', action='store_true',
                    help='force Pallas kernels (interpret mode on CPU)')
    ap.add_argument('--resident', action='store_true',
                    help='int8-resident plan: calibrate static activation '
                         'scales on the first eval batch (core/export.py)')
    ap.add_argument('--verify', nargs='?', const='strict', default=None,
                    choices=('strict', 'warn'),
                    help='run the static analyzer (repro/analysis) over '
                         'the export before serving and print the report; '
                         'strict (default) aborts on any error finding. '
                         'Implies --resident (rules read the layer plan).')
    ap.add_argument('--server', action='store_true',
                    help='request-level serving: Poisson arrivals through '
                         'the continuous-batching scheduler '
                         '(repro/serving/); implies --resident, and '
                         '--threshold none recalibrates on the stream')
    ap.add_argument('--requests', type=int, default=256,
                    help='--server: trace length')
    ap.add_argument('--rate', type=float, default=500.0,
                    help='--server: Poisson arrival rate (req/s)')
    ap.add_argument('--slots', type=int, default=32,
                    help='--server: scheduler batch slots (tile-padded)')
    ap.add_argument('--max-wait', type=float, default=0.05,
                    help='--server: run a partial batch once its oldest '
                         'request has waited this long (seconds)')
    ap.add_argument('--deadline-ms', type=float, default=None,
                    help='--server: per-request deadline after arrival; '
                         'enables the SLO layer (deadline admission + '
                         'graceful degradation through the exit heads) on '
                         'a simulated clock from measured stage costs')
    ap.add_argument('--pipeline', action='store_true',
                    help='--server: pipeline-parallel over every visible '
                         'jax device — the placement solver packs stages '
                         'onto devices by measured cost, the int8 carry '
                         'streams between them; implies --server '
                         '(simulated clock); composes with --chaos '
                         '(seeded device kill)')
    ap.add_argument('--chaos', action='store_true',
                    help='--server: run the replica pool under a seeded '
                         'fault plan (kill + straggler slowdown) and '
                         'report resilience counters; implies --server')
    ap.add_argument('--chaos-seed', type=int, default=0)
    ap.add_argument('--trace', metavar='OUT.json', default=None,
                    help='record a runtime trace (export spans + --server '
                         'scheduler spans), validate its invariants, and '
                         'write Chrome-trace JSON for Perfetto')
    ap.add_argument('--replicas', type=int, default=2,
                    help='--chaos: provisioned replica count')
    ap.add_argument('--max-replicas', type=int, default=4,
                    help='--chaos: elastic scale-up ceiling')
    args = ap.parse_args()
    if args.chaos or args.pipeline:
        args.server = True
    if args.pipeline and args.deadline_ms is not None:
        ap.error('--pipeline does not compose with --deadline-ms (the '
                 'SLO layer lives in the replica pool)')
    if args.server or args.verify:
        args.resident = True

    fam = CNNFamily(SyntheticImages())
    cfg = CNN_REGISTRY[args.config]
    params = fam.init(jax.random.key(0), cfg)
    params, cfg = fam.add_exits(jax.random.key(1), params, cfg,
                                fam.default_exit_points(cfg))
    cfg = cfg.replace(w_bits=8, a_bits=8)
    if args.steps:
        trainer = Trainer(batch=args.batch, steps=args.steps)
        params, _ = trainer.fit(fam, cfg, params)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    stream = fam.eval_batches(args.batches, args.batch)
    model = export_cnn(params, cfg, use_pallas=True if args.pallas else None,
                       calibrate=stream[0][0] if args.resident else None,
                       verify=args.verify, tracer=tracer)
    if args.verify:
        # strict mode raised inside export_cnn already; print the report
        # (incl. info findings and visible skips) either way
        print(model.analysis)
    if args.resident:
        s = model.summary()
        print(f'layer plan: {s["kernel_launches"]} kernel launches, '
              f'{s["n_fused_lowrank"]} fused low-rank, '
              f'{s["n_depthwise"]} depthwise, '
              f'fallback MACs {s["fallback_mac_fraction"]:.1%}')
    if args.server:
        return _serve_trace(model, fam, cfg, args, tracer=tracer)
    if tracer is not None:       # batch mode: export spans only
        tracer.write(args.trace)
        print(f'trace: {len(tracer.spans)} spans -> {args.trace}')
    threshold = 0.85 if args.threshold is None else args.threshold
    # warm the jit caches off the clock
    model.serve_early_exit(stream[0][0], threshold=threshold)

    stages = {s: 0 for s in cfg.exit_stages}
    hit = tot = 0
    t0 = time.perf_counter()
    for x, y in stream:
        pred, stage = model.serve_early_exit(x, threshold=threshold)
        jax.block_until_ready(pred)
        hit += int(jnp.sum(pred == y))
        tot += int(y.size)
        for s in stages:
            stages[s] += int(np.sum(np.asarray(stage) == s))
    dt = time.perf_counter() - t0

    print(f'config={cfg.name} backend={jax.default_backend()} '
          f'int8_path={"pallas" if args.pallas else "auto"}')
    print(f'served {tot} images in {dt:.3f}s '
          f'({tot / dt:.0f} img/s), acc={hit / max(tot, 1):.3f}')
    for s in sorted(stages):
        print(f'  exit@stage{s}: {stages[s] / max(tot, 1):.1%}')
    print(f'  final head:   {1 - sum(stages.values()) / max(tot, 1):.1%}')


if __name__ == '__main__':
    main()
