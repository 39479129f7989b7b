"""Chip smoke: the compress -> export -> serve path on a TPU, end to end.

Drives the system's main path once through its library entry points at
the full width and depth of the paper's headline model, ``resnet34-cifar``
(blocks (3, 4, 6, 3), widths 64-512, 32x32 inputs, exit heads after stages
1 and 2, so three serving segments), with random weights from ``--seed``:

  (a) QAT    3 ``Trainer.fit`` steps at w8/a8, batch 128; losses finite.
  (b) export ``export_cnn(..., calibrate=<batch of 128>)``: the int8-
             resident plan on the Pallas kernels (``backend == 'pallas'``),
             every stage program compiled with a Mosaic ``tpu_custom_call``.
  (c) serve  256 requests on a seeded Poisson trace through
             ``ContinuousBatchScheduler`` (64 slots, wall clock, calibrated
             exit threshold): throughput, p50/p99, exit mix.
  (d) check  every completion bit-exact against the monolithic
             ``fn_exits`` serving that request alone at the same slot
             geometry; served logits of one batch within tolerance of the
             plain fp32 ``cnn_forward`` reference.

``--chips 4`` runs only the pipeline-parallel phase and what it is
compared with: ``PipelineParallelScheduler`` over four TPU devices against
the single-device scheduler on the same trace, answer for answer, both
bit-exact against the monolithic oracle, and every placed stage's params
and outputs on the device its placement names.

Every phase prints its own line; the last line of stdout is one JSON
object ``{"ok": true, "device": {...}}``.  Without a TPU, or without the
repository next to this file, it exits non-zero and prints no result.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the pipeline phase on four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = 'resnet34-cifar'
BATCH = 128         # QAT and calibration batch
REQUESTS = 256
SLOTS = 64
RATE = 8000.0       # Poisson arrival rate (requests/s)
MAX_WAIT = 0.005    # run a partial batch once its oldest request waited (s)
# served-vs-fp32 tolerance of the resident export tests
# (tests/test_export.py: atol = 6e-2 * max(|reference|, 1))
REF_ATOL = 6e-2


def fail(msg: str):
    print(f'FAIL: {msg}', file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(cond, msg: str):
    if not cond:
        fail(msg)


def say(phase: str, **kv):
    print(f'[{phase}] ' + ' '.join(f'{k}={v}' for k, v in kv.items()),
          flush=True)


def tpu_devices(n: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == 'tpu',
          f'JAX platform is {devs[0].platform!r}, not a TPU; this smoke '
          f'runs on the chip only')
    check(len(devs) >= n, f'{len(devs)} TPU device(s), need {n}')
    return devs[:n]


def build(seed: int):
    """resnet34-cifar with its default exit heads, w8/a8 QAT config."""
    import jax
    from repro.configs.cnn import CNN_REGISTRY
    from repro.core.family import CNNFamily
    from repro.data import SyntheticImages
    fam = CNNFamily(SyntheticImages(seed=seed))
    cfg = CNN_REGISTRY[CONFIG]
    params = fam.init(jax.random.key(seed), cfg)
    params, cfg = fam.add_exits(jax.random.key(seed + 1), params, cfg,
                                fam.default_exit_points(cfg))
    return fam, params, cfg.replace(w_bits=8, a_bits=8)


def export(params, cfg, calib):
    """The int8-resident export on the Pallas kernels, in three segments."""
    from repro.core.export import export_cnn
    t0 = time.perf_counter()
    model = export_cnn(params, cfg, calibrate=calib)
    check(model.backend == 'pallas',
          f'export backend is {model.backend!r}, not the Pallas kernels')
    check(model.n_stages == 3, f'{model.n_stages} serving segments, want 3')
    return model, time.perf_counter() - t0


def compile_stages(model, x):
    """Cold-compile every stage program at the geometry of ``x`` and prove
    each holds Mosaic kernels; returns the compile seconds per stage."""
    import jax
    secs, carry = [], x
    for k in range(model.n_stages):
        t0 = time.perf_counter()
        compiled = model.stage_fns[k].lower(model.params, carry).compile()
        secs.append(time.perf_counter() - t0)
        n_kernels = compiled.as_text().count('tpu_custom_call')
        check(n_kernels > 0, f'stage {k} program has no tpu_custom_call')
        if k < model.n_stages - 1:
            _, carry = jax.block_until_ready(model.run_stage(k, carry))
    return secs


def oracle_mismatches(model, completions, xs, threshold, slots):
    """Requests whose served answer differs from the monolithic
    ``fn_exits`` serving that request alone, padded to ``slots``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import exit_decisions
    bad = []
    pad = jnp.zeros((slots - 1,) + xs.shape[1:], xs.dtype)
    for rid, c in sorted(completions.items()):
        logits, exits = model.fn_exits(model.params,
                                       jnp.concatenate([xs[rid][None], pad]))
        stage, ans = exit_decisions(logits, exits, threshold)
        if c.exit_stage != int(stage[0]) or not np.array_equal(c.logits,
                                                               ans[0]):
            bad.append(rid)
    return bad


def poisson_trace(xs, rate, seed):
    import numpy as np
    from repro.serving import Request
    t = np.cumsum(np.random.default_rng(seed).exponential(
        1.0 / rate, size=xs.shape[0]))
    return [Request(i, xs[i], float(t[i])) for i in range(xs.shape[0])]


def one_chip(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.export import calibrate_exit_threshold
    from repro.core.passes import Trainer
    from repro.models.cnn import cnn_forward
    from repro.serving import ContinuousBatchScheduler

    (dev,) = tpu_devices(1)
    fam, params, cfg = build(seed)

    # (a) QAT through the chain's trainer
    losses = []
    t0 = time.perf_counter()
    params, _ = Trainer(batch=BATCH, steps=3, seed=seed).fit(
        fam, cfg, params, losses=losses)
    check(len(losses) == 3 and all(math.isfinite(v) for v in losses),
          f'QAT losses not finite: {losses}')
    say('qat', config=cfg.name, steps=3, batch=BATCH,
        losses=[round(v, 6) for v in losses], finite=True,
        seconds=round(time.perf_counter() - t0, 3))

    # (b) int8-resident export calibrated on one batch
    calib = fam.eval_batches(1, BATCH, seed=seed + 100)[0][0]
    model, export_s = export(params, cfg, calib)
    s = model.summary()
    xs = jnp.concatenate([x for x, _ in fam.eval_batches(
        -(-REQUESTS // BATCH), BATCH, seed=seed + 200)]
    )[:REQUESTS]
    compile_s = compile_stages(model, xs[:SLOTS])
    say('export', backend=model.backend, stages=model.n_stages,
        kernel_launches=s['kernel_launches'],
        fused_lowrank=s['n_fused_lowrank'],
        fallback_mac_fraction=s['fallback_mac_fraction'],
        tpu_custom_call_in_every_stage=True,
        export_seconds=round(export_s, 3),
        cold_compile_seconds=[round(v, 3) for v in compile_s])

    # (c) the request scheduler on the wall clock
    threshold = calibrate_exit_threshold(model, xs[:SLOTS])
    sched = ContinuousBatchScheduler(model, slots=SLOTS,
                                     threshold=threshold,
                                     max_wait=MAX_WAIT)
    # warm every stage program off the clock: nothing exits at 2.0
    ContinuousBatchScheduler(model, slots=SLOTS, threshold=2.0
                             ).run_trace(poisson_trace(xs[:4], 1e3, 1))
    completions, metrics = sched.run_trace(
        poisson_trace(xs, RATE, seed))
    m = metrics.summary()
    check(len(completions) == REQUESTS,
          f'{len(completions)}/{REQUESTS} requests completed')
    say('serve', requests=f'{len(completions)}/{REQUESTS}',
        slots=sched.slots, rate_rps=RATE,
        threshold=round(threshold, 6), clock='wall',
        throughput_rps=round(m['throughput_rps'], 1),
        p50_ms=round(m['p50_latency_s'] * 1e3, 3),
        p99_ms=round(m['p99_latency_s'] * 1e3, 3),
        exit_mix=json.dumps(m['exit_mix'], sort_keys=True).replace(' ', ''))

    # (d) correctness on the chip
    bad = oracle_mismatches(model, completions, xs, threshold, sched.slots)
    check(not bad, f'{len(bad)} requests differ from the monolithic '
                   f'oracle, first {bad[:8]}')
    say('oracle', bit_exact=f'{REQUESTS - len(bad)}/{REQUESTS}')
    xb = xs[:SLOTS]
    served = np.asarray(model.serve(xb))
    fp32 = cfg.replace(w_bits=0, a_bits=0)
    with jax.default_matmul_precision('float32'):
        ref = np.asarray(jax.jit(lambda p, x: cnn_forward(p, fp32, x))(
            params, xb))
    err = float(np.max(np.abs(served - ref)))
    tol = REF_ATOL * max(float(np.max(np.abs(ref))), 1.0)
    check(np.all(np.isfinite(served)), 'served logits not finite')
    check(err <= tol, f'served vs fp32 reference: max abs err {err} > {tol}')
    say('reference', batch=xb.shape[0], max_abs_err=round(err, 6),
        atol=round(tol, 6), within=True)
    return dev


def four_chips(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.export import calibrate_exit_threshold
    from repro.serving import (ContinuousBatchScheduler,
                               PipelineParallelScheduler)

    devs = tpu_devices(4)
    fam, params, cfg = build(seed)
    calib = fam.eval_batches(1, BATCH, seed=seed + 100)[0][0]
    model, _ = export(params, cfg, calib)
    xs = jnp.concatenate([x for x, _ in fam.eval_batches(
        -(-REQUESTS // BATCH), BATCH, seed=seed + 200)]
    )[:REQUESTS]
    x0 = xs[:SLOTS]
    compile_stages(model, x0)
    threshold = calibrate_exit_threshold(model, x0)

    # per-stage batch cost on one chip: the placement solver's input
    costs, carry = [], x0
    for k in range(model.n_stages):
        out = jax.block_until_ready(model.run_stage(k, carry))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(model.run_stage(k, carry))
            ts.append(time.perf_counter() - t0)
        costs.append(float(np.median(ts)))
        if k < model.n_stages - 1:
            carry = out[1]

    trace = poisson_trace(xs, RATE, seed)
    single = ContinuousBatchScheduler(model, slots=SLOTS,
                                      threshold=threshold, stage_costs=costs)
    s_comp, _ = single.run_trace(trace)
    pipe = PipelineParallelScheduler(model, slots=SLOTS,
                                     threshold=threshold, stage_costs=costs,
                                     devices=devs)
    p_comp, _ = pipe.run_trace(poisson_trace(xs, RATE, seed))
    for name, comp in (('single', s_comp), ('pipeline', p_comp)):
        check(len(comp) == REQUESTS,
              f'{name}: {len(comp)}/{REQUESTS} completed')

    # placement: each stage's params and outputs on its named TPU device
    placed = pipe.model
    names = []
    carry = x0
    for k in range(placed.n_stages):
        dev = devs[pipe.stage_dev[k]]
        check(placed.stage_devices[k] == dev,
              f'stage {k} pinned to {placed.stage_devices[k]}, placement '
              f'names {dev}')
        for leaf in jax.tree.leaves(placed.stage_params[k]):
            check(leaf.devices() == {dev},
                  f'stage {k} params on {leaf.devices()}, not {dev}')
        out = placed.run_stage(k, jax.device_put(carry, dev))
        for leaf in jax.tree.leaves(out):
            check(leaf.devices() == {dev},
                  f'stage {k} output on {leaf.devices()}, not {dev}')
        if k < placed.n_stages - 1:
            carry = out[1]
        names.append(f'{k}->{dev.platform}:{dev.id}')
    check(len({pipe.stage_dev[k] for k in range(placed.n_stages)})
          == placed.n_stages, 'stages share a device; want one each')
    say('placement', stages=','.join(names),
        loads=[round(v * 1e3, 4) for v in pipe.placement.loads],
        idle_devices=sum(1 for v in pipe.placement.loads if v == 0.0))

    agree = sum(1 for r in trace
                if p_comp[r.rid].exit_stage == s_comp[r.rid].exit_stage
                and np.array_equal(p_comp[r.rid].logits,
                                   s_comp[r.rid].logits))
    check(agree == REQUESTS,
          f'pipeline and single-device disagree on '
          f'{REQUESTS - agree} requests')
    for name, comp in (('single', s_comp), ('pipeline', p_comp)):
        bad = oracle_mismatches(model, comp, xs, threshold, SLOTS)
        check(not bad, f'{name}: {len(bad)} requests differ from the '
                       f'monolithic oracle, first {bad[:8]}')
    say('pipeline', requests=REQUESTS, devices=len(devs),
        stage_costs_ms=[round(c * 1e3, 4) for c in costs],
        pipeline_vs_single=f'{agree}/{REQUESTS}',
        oracle_bit_exact='single,pipeline')
    return devs[0]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4),
                    help='4: only the pipeline-parallel phase, on 4 chips')
    ap.add_argument('--seed', type=int, default=0,
                    help='seed of the random weights, data and trace')
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, 'src'))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        fail(f'the repository is not next to this script ({e})')
    use_compile_cache()
    dev = (four_chips(args.seed) if args.chips == 4
           else one_chip(args.seed))
    import jax
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': len(jax.devices())}}), flush=True)


if __name__ == '__main__':
    main()
