"""Serving-load benchmark: static batching vs early-exit slot compaction.

Drives the request-level serving runtime (repro/serving/) with a Poisson
arrival trace against an int8-resident exported CNN and A/Bs the two
schedulers on the SAME trace:

* ``static``     — full batches through the monolithic ``fn_exits``; the
  early-exit rule picks which head answers but every slot pays full depth.
* ``compacting`` — the stage-split plan: exited samples complete after
  their segment, survivors are compacted, freed slots backfill from the
  queue (ContinuousBatchScheduler).

Methodology on a noisy CI box: per-stage batch costs and the monolithic
batch cost are measured as **medians over --iters runs** at the fixed slot
geometry, then a simulated single-executor clock replays the trace on
those medians — the A/B cannot be corrupted by a concurrent load spike,
and the numbers are reproducible.  The data path is still executed for
real: every request's answer is checked bit-exact against the monolithic
model serving that request alone at the same slot geometry (the resident
export's bit-exactness contract; --oracle-all checks every request,
otherwise a sample).

Results go to BENCH_load.json (backend, batch geometry, median timings,
per-scheduler latency/throughput/occupancy, plus a windowed ``timeseries``
block per scheduler — queue depth, rolling p99, occupancy over the run —
that ``benchmarks/summarize.py --diff-bench`` compares across
generations).  ``--smoke`` is the CI wiring: a tiny trace, asserts the
scheduler drains the queue and answers match the oracle, writes nothing
unless --out is given.

``--trace out.json`` records the run (the compacting scheduler, or the
chaos-on pool run under --chaos) as Chrome-trace JSON, validates its span
invariants strictly (``repro.obs.check_trace`` — including a round-trip
through the written file), and prints a one-line telemetry digest.

``--chaos`` switches to the resilience benchmark over the replica pool
(repro/serving/replica.py): the model is served from a persisted chain
checkpoint through the registry, a bursty oversubscribed trace drives an
elastic pool, and a seeded :class:`ChaosPlan` injects a mid-batch replica
kill (failover restores a replacement through
``ModelRegistry.restore`` — the chain-checkpoint path) plus a straggler
slowdown (flagged and de-prioritized by the EWMA monitor).  Three runs on
the same trace: chaos-off baseline, chaos-on (asserted zero-loss and
bit-exact vs the baseline AND the request-alone oracle), and chaos-on
with deadlines (asserted never-late: every deadline request is on time,
degraded through an exit head, or rejected at admission).  Results go to
BENCH_chaos.json (availability, SLO attainment, degraded-exit mix,
failover count, p99 chaos-on vs chaos-off).

    PYTHONPATH=src python benchmarks/serving_load.py [--slots 32] [--requests 512]
    PYTHONPATH=src python benchmarks/serving_load.py --chaos
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import median_us as _median_us  # noqa: E402  (shared convention)


def measure_stage_costs(model, x, iters=10):
    """Median per-segment batch cost (us) at the batch geometry of ``x``,
    feeding each segment the real carry of the previous one, plus the
    monolithic ``fn_exits`` cost on the same batch."""
    costs, carry = [], x
    for k in range(model.n_stages):
        costs.append(_median_us(model.stage_fns[k], model.params, carry,
                                iters=iters))
        if k < model.n_stages - 1:
            _, carry = model.run_stage(k, carry)
    mono = _median_us(model.fn_exits, model.params, x, iters=iters)
    return costs, mono


def poisson_trace(xs, rate, seed=0):
    """Requests over ``xs`` with exponential inter-arrival times (rate/s)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, size=xs.shape[0]))
    return [Request(i, xs[i], float(t[i])) for i in range(xs.shape[0])]


def burst_trace(xs, rate, seed=0, n_bursts=2, burst=8):
    """Poisson arrivals with injected spikes: ``n_bursts`` groups of
    ``burst`` consecutive requests arrive at the same instant (the chaos
    benchmark's arrival-burst element)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=xs.shape[0])
    n = xs.shape[0]
    for b in range(n_bursts):
        s = int((b + 1) * n / (n_bursts + 1))
        gaps[s:min(s + burst, n)] = 0.0
    t = np.cumsum(gaps)
    return [Request(i, xs[i], float(t[i])) for i in range(n)]


def check_oracle(model, completions, reqs, threshold, slots):
    """Every sampled request's answer must be bit-exact vs the monolithic
    model serving that request ALONE, padded to the same slot geometry."""
    from repro.serving import exit_decisions
    bad = []
    for r in reqs:
        xb = jnp.concatenate([r.x[None],
                              jnp.zeros((slots - 1,) + r.x.shape,
                                        r.x.dtype)])
        logits, exits = model.fn_exits(model.params, xb)
        stage, ans = exit_decisions(logits, exits, threshold)
        c = completions[r.rid]
        if c.exit_stage != int(stage[0]) or not np.array_equal(
                c.logits, ans[0]):
            bad.append(r.rid)
    return bad


def validate_and_write_trace(tracer, completions, path, *,
                             require_failover=False):
    """Strict invariant check on the recorded spans, write the Chrome
    trace, and re-validate what was actually written (round-trip through
    the exporter/parser).  ``require_failover`` additionally asserts the
    chaos story is visible: a killed ``stage.exec`` on the dead replica's
    track and a ``failover.restore`` span on the replacement's."""
    from repro.obs import check_trace, load_chrome_trace
    check_trace(tracer, completions, strict=True)
    if require_failover:
        killed = [s for s in tracer.spans
                  if s.name == 'stage.exec' and s.args.get('killed')]
        restores = [s for s in tracer.spans
                    if s.name == 'failover.restore']
        assert killed, 'chaos trace has no killed stage.exec span'
        assert restores, 'chaos trace has no failover.restore span'
        assert all(s.track.startswith('replica') for s in killed + restores)
    tracer.write(path)
    check_trace(load_chrome_trace(path), completions, strict=True)
    print(f'  trace: {len(tracer.spans)} spans -> {path} '
          f'(validated, open at https://ui.perfetto.dev)')


def run_chaos(args, fam, cfg, params, xs, calib, threshold, stage_costs_us,
              slots, use_pallas, out):
    """The --chaos path: three replica-pool runs on one bursty trace.

    A: chaos off (the undisturbed baseline).  B: seeded kill + straggler
    slowdown — must drain with zero lost requests, every answer bit-exact
    vs A and vs the request-alone oracle, failover restoring through the
    registry's chain checkpoint.  C: B plus per-request deadlines — the
    SLO layer must keep every admitted request on time (degrading through
    the exit heads when the budget runs short), never silently late.
    """
    import tempfile

    from repro.checkpoint import save_chain_state
    from repro.core.passes import ChainState
    from repro.serving import (ChaosPlan, ModelRegistry,
                               ReplicaPoolScheduler, Request, SLOPolicy)

    # serve from a persisted chain checkpoint so failover exercises the
    # real restore path (registry -> chain_io -> re-export)
    ckpt = tempfile.mkdtemp(prefix='chaos_ckpt_')
    st = ChainState(family=fam, cfg=cfg, params=params,
                    key=jax.random.key(7), exit_threshold=threshold)
    save_chain_state(ckpt, st, step=0)
    reg = ModelRegistry()
    model = reg.load('cnn', ckpt, fam, use_pallas=use_pallas,
                     calibrate=calib)

    costs = [c * 1e-6 for c in stage_costs_us]
    # oversubscribe the MAXED-OUT pool 2x: replicas stay busy for the
    # whole trace (the seeded kill is guaranteed to land mid-batch) and
    # elastic scaling is driven to its ceiling
    rate = args.rate or 2.0 * args.max_replicas * slots / sum(costs)
    trace = burst_trace(xs, rate, seed=0, burst=max(slots, 8))
    pool_kw = dict(slots=slots, threshold=threshold, stage_costs=costs,
                   replicas=args.replicas, min_replicas=args.replicas,
                   max_replicas=args.max_replicas,
                   restore=lambda: reg.restore('cnn'),
                   restore_delay=costs[0])

    base_comp, base_met = ReplicaPoolScheduler(
        model, **pool_kw).run_trace(trace)
    assert len(base_comp) == len(trace), 'baseline run lost requests'

    # chaos times are fractions of the baseline run's MEASURED makespan,
    # not the arrival horizon — on an oversubscribed trace most serving
    # happens in the drain phase, and an a-priori work estimate misses
    # how much early exits shrink it (a kill seeded past the true
    # makespan would never fire)
    makespan = max(c.t_done for c in base_comp.values())
    plan = ChaosPlan.seeded(args.chaos_seed, args.replicas, makespan)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    chaos_comp, chaos_met = ReplicaPoolScheduler(
        model, chaos=plan, tracer=tracer, **pool_kw).run_trace(trace)
    b_sum, c_sum = base_met.summary(), chaos_met.summary()
    res = c_sum['resilience']
    assert len(chaos_comp) == len(trace), 'chaos run lost requests'
    assert c_sum['availability'] == 1.0, 'chaos run rejected requests'
    assert res['kills'] >= 1 and res['failovers'] >= 1, 'no kill fired'
    assert any(i.get('mid_batch') for k, _, i in chaos_met.events
               if k == 'kill'), 'kill landed on an idle replica'
    assert res['straggler_flags'] >= 1, 'slowdown never flagged'
    for r in trace:
        b, c = base_comp[r.rid], chaos_comp[r.rid]
        assert c.exit_stage == b.exit_stage and np.array_equal(
            c.logits, b.logits), f'request {r.rid} diverged under chaos'
    oracle_reqs = (trace if (args.smoke or args.oracle_all)
                   else trace[:: max(1, len(trace) // 16)])
    bad = check_oracle(model, chaos_comp, oracle_reqs, threshold, slots)
    assert not bad, f'chaos: requests {bad[:8]} diverge from oracle'

    full_cost = sum(costs)
    rng = np.random.default_rng(args.chaos_seed + 1)
    budgets = full_cost * rng.uniform(0.5, 6.0, size=len(trace))
    slo_trace = [Request(r.rid, r.x, r.t_arrival,
                         deadline=r.t_arrival + float(budgets[i]))
                 for i, r in enumerate(trace)]
    slo_comp, slo_met = ReplicaPoolScheduler(
        model, chaos=plan, slo=SLOPolicy(), **pool_kw).run_trace(slo_trace)
    s_sum = slo_met.summary()
    b_sum['timeseries'] = base_met.timeseries()
    c_sum['timeseries'] = chaos_met.timeseries()
    s_sum['timeseries'] = slo_met.timeseries()
    assert s_sum['slo']['n_late'] == 0, 'never-late contract violated'
    for c in slo_comp.values():
        if not c.degraded:
            b = base_comp[c.rid]
            assert c.exit_stage == b.exit_stage and np.array_equal(
                c.logits, b.logits), \
                f'request {c.rid} diverged under chaos+SLO'

    results = {
        'backend': jax.default_backend(),
        'int8_path': 'pallas' if use_pallas else 'jnp-ref',
        'config': cfg.name,
        'batch_geometry': {'slots': slots, 'image': [32, 32, 3]},
        'n_requests': len(trace),
        'arrival_rate_rps': round(rate, 3),
        'exit_threshold': round(threshold, 6),
        'pool': {'replicas': args.replicas, 'min_replicas': args.replicas,
                 'max_replicas': args.max_replicas},
        'timing': {'iters': args.iters, 'reduction': 'median',
                   'stage_costs_us': [round(c, 1) for c in stage_costs_us]},
        'chaos_plan': {'seed': args.chaos_seed,
                       'kills': [list(k) for k in plan.kills],
                       'slowdowns': [list(s) for s in plan.slowdowns]},
        'deadline_budget_x_full_depth': [0.5, 6.0],
        'chaos_off': b_sum,
        'chaos_on': c_sum,
        'chaos_slo': s_sum,
        'availability': c_sum['availability'],
        'slo_attainment': s_sum['slo']['attainment'],
        'degraded_exit_mix': s_sum['degraded_exit_mix'],
        'failovers': res['failovers'],
        'p99_chaos_off_s': b_sum['p99_latency_s'],
        'p99_chaos_on_s': c_sum['p99_latency_s'],
        'chaos_p99_x': round(c_sum['p99_latency_s']
                             / max(b_sum['p99_latency_s'], 1e-12), 3),
    }
    print(f"{cfg.name} slots={slots} rate={rate:.0f}/s pool="
          f"{args.replicas}..{args.max_replicas} replicas")
    print(f"  chaos off: p99={b_sum['p99_latency_s'] * 1e3:.2f}ms "
          f"throughput={b_sum['throughput_rps']:.0f} req/s")
    print(f"  chaos on:  p99={c_sum['p99_latency_s'] * 1e3:.2f}ms "
          f"({results['chaos_p99_x']:.2f}x) availability="
          f"{c_sum['availability']:.4f} kills={res['kills']} "
          f"failovers={res['failovers']} "
          f"straggler_flags={res['straggler_flags']} "
          f"peak_replicas={res['peak_replicas']}")
    print(f"  chaos+SLO: attainment={s_sum['slo']['attainment']:.4f} "
          f"on_time={s_sum['slo']['n_on_time']} "
          f"late={s_sum['slo']['n_late']} "
          f"degraded={s_sum['n_degraded']} "
          f"rejected={s_sum['n_rejected']} "
          f"degraded_mix={s_sum['degraded_exit_mix']}")
    print('  ' + chaos_met.telemetry_digest())
    if tracer is not None:
        validate_and_write_trace(tracer, chaos_comp, args.trace,
                                 require_failover=True)
    if args.smoke:
        print('chaos smoke OK: zero lost, bit-exact under kill+straggler, '
              'no late completion')
    if out:
        with open(out, 'w') as f:
            json.dump(results, f, indent=1)
        print(f'wrote {out}')


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs.cnn import CNN_REGISTRY
    from repro.core.export import calibrate_exit_threshold, export_cnn
    from repro.core.family import CNNFamily
    from repro.data import SyntheticImages
    from repro.kernels.tiling import batch_slots
    from repro.serving import ContinuousBatchScheduler, StaticBatchScheduler

    ap = argparse.ArgumentParser()
    ap.add_argument('--config', default='resnet8-cifar',
                    choices=sorted(CNN_REGISTRY))
    ap.add_argument('--slots', type=int, default=32)
    ap.add_argument('--requests', type=int, default=512)
    ap.add_argument('--iters', type=int, default=10)
    ap.add_argument('--rate', type=float, default=None,
                    help='arrival rate (req/s); default 2x the static '
                         'service capacity — heavy traffic, so each '
                         'scheduler completes at its own capacity and the '
                         'A/B measures service rate, not arrival rate')
    ap.add_argument('--threshold', type=float, default=None,
                    help='exit threshold; default calibrates to the batch-'
                         'median first-head confidence')
    ap.add_argument('--quantile', type=float, default=0.5,
                    help='calibration target: fraction exiting at head 1')
    ap.add_argument('--pallas', action='store_true',
                    help='force Pallas kernels (interpret mode on CPU)')
    ap.add_argument('--oracle-all', action='store_true',
                    help='oracle-check every request (default: 16 sampled)')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny CI run: 24 requests, 8 slots, 2 iters, '
                         'asserts drain + bit-exact answers, no file '
                         'output unless --out is given')
    ap.add_argument('--chaos', action='store_true',
                    help='resilience benchmark: replica pool under seeded '
                         'kill + straggler + bursts (BENCH_chaos.json)')
    ap.add_argument('--chaos-seed', type=int, default=0)
    ap.add_argument('--replicas', type=int, default=2,
                    help='--chaos: initial replica count')
    ap.add_argument('--max-replicas', type=int, default=4,
                    help='--chaos: elastic scaling ceiling')
    ap.add_argument('--trace', default=None, metavar='OUT.json',
                    help='record the run (compacting scheduler, or the '
                         'chaos-on pool run under --chaos) as Chrome-trace '
                         'JSON, strictly validated via repro.obs')
    ap.add_argument('--out', default=None)
    args = ap.parse_args()
    if args.smoke:
        args.slots, args.requests, args.iters = 8, 24, 2
        if args.chaos:
            args.requests = 32        # enough in-flight work for the kill
    out = args.out
    if out is None and not args.smoke:
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
            'BENCH_chaos.json' if args.chaos else 'BENCH_load.json')

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

    if args.chaos:
        return run_chaos(args, fam, cfg, params, xs, calib, threshold,
                         stage_costs_us, slots, use_pallas, out)

    # service capacities (req/s) from the median costs and the calibration
    # batch's exit mix: static pays the monolithic cost for every slot;
    # compacting pays segment k only for the fraction still alive there.
    from repro.serving import exit_decisions
    logits_c, exits_c = model.fn_exits(model.params, calib)
    stage_c, _ = exit_decisions(logits_c, exits_c, threshold)
    alive, cost_per_batch = 1.0, 0.0
    for k in range(model.n_stages):
        cost_per_batch += alive * stage_costs_us[k]
        if k < model.n_stages - 1:
            s = model.stage_exits[k]
            alive *= 1.0 - float(np.mean(stage_c == s))
    cap_static = slots / (mono_us * 1e-6)
    cap_compact = slots / (cost_per_batch * 1e-6)
    rate = args.rate or 2.0 * cap_static
    trace = poisson_trace(xs, rate, seed=0)

    static = StaticBatchScheduler(model, slots=slots, threshold=threshold,
                                  batch_cost=mono_us * 1e-6)
    s_comp, s_met = static.run_trace(trace)
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    compacting = ContinuousBatchScheduler(
        model, slots=slots, threshold=threshold,
        stage_costs=[c * 1e-6 for c in stage_costs_us], tracer=tracer)
    c_comp, c_met = compacting.run_trace(trace)

    assert len(s_comp) == len(c_comp) == args.requests, \
        'scheduler failed to drain the queue'
    oracle_reqs = (trace if (args.smoke or args.oracle_all)
                   else trace[:: max(1, len(trace) // 16)])
    for name, comp in (('static', s_comp), ('compacting', c_comp)):
        bad = check_oracle(model, comp, oracle_reqs, threshold, slots)
        assert not bad, f'{name}: requests {bad[:8]} diverge from oracle'
    agree = all(s_comp[r.rid].exit_stage == c_comp[r.rid].exit_stage
                and np.array_equal(s_comp[r.rid].logits,
                                   c_comp[r.rid].logits) for r in trace)
    assert agree, 'static and compacting schedulers disagree on answers'

    s_sum, c_sum = s_met.summary(), c_met.summary()
    s_sum['timeseries'] = s_met.timeseries()
    c_sum['timeseries'] = c_met.timeseries()
    results = {
        'backend': jax.default_backend(),
        'int8_path': 'pallas' if use_pallas else 'jnp-ref',
        'config': cfg.name,
        'batch_geometry': {'slots_requested': args.slots,
                           'slots_padded': slots,
                           'image': [32, 32, 3]},
        'n_requests': args.requests,
        'arrival_rate_rps': round(rate, 3),
        'exit_threshold': round(threshold, 6),
        'timing': {'iters': args.iters, 'reduction': 'median',
                   'stage_costs_us': [round(c, 1) for c in stage_costs_us],
                   'monolithic_us': round(mono_us, 1)},
        'capacity_static_rps': round(cap_static, 3),
        'capacity_compacting_rps': round(cap_compact, 3),
        'static': s_sum,
        'compacting': c_sum,
        'compaction_throughput_x': round(
            c_sum['throughput_rps'] / max(s_sum['throughput_rps'], 1e-9), 3),
        'compaction_p99_x': round(
            s_sum['p99_latency_s'] / max(c_sum['p99_latency_s'], 1e-9), 3),
    }
    print(f"{cfg.name} slots={slots} rate={rate:.0f}/s "
          f"exit_fraction={c_sum['exit_fraction']:.2f}")
    print(f"  static:     {s_sum['throughput_rps']:.0f} req/s  "
          f"p50={s_sum['p50_latency_s'] * 1e3:.2f}ms "
          f"p99={s_sum['p99_latency_s'] * 1e3:.2f}ms")
    print(f"  compacting: {c_sum['throughput_rps']:.0f} req/s  "
          f"p50={c_sum['p50_latency_s'] * 1e3:.2f}ms "
          f"p99={c_sum['p99_latency_s'] * 1e3:.2f}ms "
          f"occupancy={c_sum['batch_occupancy']}")
    print(f"  compaction: {results['compaction_throughput_x']:.2f}x "
          f"throughput, {results['compaction_p99_x']:.2f}x p99")
    print('  ' + c_met.telemetry_digest())
    if tracer is not None:
        validate_and_write_trace(tracer, c_comp, args.trace)
    if args.smoke:
        print('smoke OK: queue drained, answers bit-exact vs oracle')

    if out:
        with open(out, 'w') as f:
            json.dump(results, f, indent=1)
        print(f'wrote {out}')


if __name__ == '__main__':
    main()
