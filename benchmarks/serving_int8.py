"""Serving benchmark: fake-quant fp32 forward vs the exported int8 paths.

The chain's Q pass is only *analytically* cheaper until export: the QAT
forward runs fp32 convs and recomputes per-channel weight abs-max scales on
every call.  This benchmark times, per CNN config:

* ``fakequant_fp32``  — the QAT forward (per-call weight scale recompute)
* ``exported_int8``   — the PR-1 dynamic-scale export (static weight
  scales, one activation abs-max per layer, fp32 between layers)
* ``int8_resident``   — the layer-plan export (core/export.py
  ``calibrate=...``): static activation scales, requantize epilogues,
  int8 activations between layers, folded constants on the CPU backend
* ``exported_int8_early_exit`` — batched early-exit serving (resnet8);
  if no sample exits at the configured threshold, the benchmark warns and
  recalibrates the threshold to the batch's median exit confidence so the
  E pass is actually exercised
* ``lowrank_fused`` / ``lowrank_two_launch`` — the factored ('L' pass)
  model served with the one-launch fused kernel (forced via
  ``select_kernels='fused'``) vs the chained pair (``fuse_lowrank=False``);
  the measured ``winner`` and the per-layer choice the default cost model
  would make (``model_selection``) are both recorded, so the A/B shows
  whether export-time selection ships the faster lowering.  The two
  lowerings are identical on the CPU jnp backend — the A/B becomes real
  on TPU, where the launch counts differ; tests pin them.

``--smoke`` additionally asserts the zero-fp32 contract: mobilenet's plan
must report ``fallback_mac_fraction == 0`` (depthwise serves on the int8
kernel), and a ``select_kernels='measure'`` export must never record a
choice that its own measurements say is slower (selection consistency).

``--breakdown`` adds a per-layer table (im2col/patch-materialization cost
vs kernel cost — the resnet8 int8 regression of PR 1 lived there) and the
v5e roofline estimate for the fp32-roundtrip vs int8-resident HBM traffic.
``--smoke`` runs a tiny batch with 2 iterations and writes nothing unless
``--out`` is given (the scripts/ci.sh wiring).

Timings are medians over ``--iters`` runs (CI boxes are noisy).

Results go to BENCH_serving.json at the repo root.

    PYTHONPATH=src python benchmarks/serving_int8.py [--batch 64] [--pallas]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import median_us as _time  # noqa: E402  (shared convention)


def _early_exit_entry(m, x, iters, threshold=0.85):
    """Time batched early-exit serving; calibrate the threshold when the
    configured one never fires (ChainState.exit_threshold must actually be
    exercised at batch serving, not silently bypass every sample).

    The model is NOT mutated: the benchmark threshold is passed into the
    serving call, and a recalibrated operating point is *returned* in the
    entry (``exit_threshold_calibrated``).  A caller holding the chain
    should persist that value to ``ChainState.exit_threshold`` (which
    ``export_chain`` threads into future exports) — a benchmark has no
    business rewriting a live ServingModel behind its owner's back."""
    from repro.core.export import calibrate_exit_threshold, early_exit_batch

    def ee(p, x, thr):
        logits, exits = m.fn_exits(p, x)
        return early_exit_batch(logits, exits, thr)

    jee = jax.jit(ee, static_argnums=(2,))
    us = _time(jee, m.params, x, threshold, iters=iters)
    _, stage = jee(m.params, x, threshold)
    frac = float(jnp.mean(stage >= 0))
    entry = {'exported_int8_early_exit_us': round(us, 1),
             'exit_threshold': threshold,
             'exit_fraction': round(frac, 3)}
    if frac == 0.0:
        # the threshold never fires on this input distribution: recalibrate
        # to the median confidence of the earliest exit head and re-run
        thr = calibrate_exit_threshold(m, x)
        print(f'  WARNING: no sample exited at threshold {threshold:.2f}; '
              f'recalibrated to batch-median confidence {thr:.3f}')
        us2 = _time(jee, m.params, x, thr, iters=iters)
        _, stage2 = jee(m.params, x, thr)
        entry.update(
            exit_threshold_calibrated=round(thr, 4),
            exit_fraction_calibrated=round(float(jnp.mean(stage2 >= 0)), 3),
            exported_int8_early_exit_calibrated_us=round(us2, 1))
    return entry


def _breakdown(m, x, iters, use_pallas):
    """Per-layer costs from the layer plan: patch materialization (im2col)
    vs the int8 kernel, over the exact serving shapes and the same
    lowering (Pallas vs jnp reference) as the timed serving fn."""
    from repro.kernels import ops
    from repro.kernels.quant_conv import im2col_nhwc
    rows = []
    for name, e in m.plan.layers.items():
        if e['kind'] != 'conv' or e['factored']:
            continue
        cin, cout = e['in_shape'][-1], e['out_shape'][-1]
        kh, kw = e['kernel']
        x_q = jnp.zeros(e['in_shape'], jnp.int8)
        if e['fallback']:
            # fallback layers never materialize im2col patches (they serve
            # via lax.conv directly on NHWC) — no costs to attribute
            # beyond the declared fp32 conv itself
            us_i = us_k = None
        elif e.get('depthwise'):
            # depthwise is the direct (non-im2col) int8 kernel: no patch
            # cost at all, just the per-channel VPU kernel
            sw = jnp.ones((cout,), jnp.float32)
            us_i = 0.0
            conv = jax.jit(lambda v, s=e['stride'], sx=e['sx'], sw=sw:
                           ops.depthwise_conv_static(
                               v, jnp.zeros((kh, kw, 1, cout), jnp.int8),
                               sw, sx=sx, stride=s, use_pallas=use_pallas))
            us_k = round(_time(conv, x_q, iters=iters), 1)
        else:
            w_q = jnp.zeros((kh, kw, cin, cout), jnp.int8)
            sw = jnp.ones((cout,), jnp.float32)
            im2col = jax.jit(lambda v, k=(kh, kw), s=e['stride']:
                             im2col_nhwc(v, k[0], k[1], s)[0])
            us_i = round(_time(im2col, x_q, iters=iters), 1)
            conv = jax.jit(lambda v, wq=w_q, s=e['stride'], sx=e['sx']:
                           ops.quant_conv_static(v, wq, sw, sx=sx, stride=s,
                                                 use_pallas=use_pallas))
            us_k = round(_time(conv, x_q, iters=iters), 1)
        rows.append({'layer': name, 'in_shape': list(e['in_shape']),
                     'macs': e['macs'], 'im2col_us': us_i,
                     'kernel_us': us_k, 'fallback': e['fallback'],
                     'depthwise': bool(e.get('depthwise'))})
        print(f"  {name:14s} in={str(e['in_shape']):>18s} "
              f"macs={e['macs']:>10d} "
              + ('fallback (no im2col)' if e['fallback'] else
                 f'im2col={us_i:8.1f}us kernel={us_k:8.1f}us'
                 + (' [depthwise]' if e.get('depthwise') else '')))
    return rows


def main():
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs.cnn import (MOBILENET_SMALL_CIFAR, RESNET8_CIFAR,
                                   VGG8_CIFAR)
    from repro.core.export import export_cnn
    from repro.core.family import CNNFamily
    from repro.data import SyntheticImages
    from repro.models.cnn import cnn_forward, init_cnn
    from roofline import int8_serving_roofline

    ap = argparse.ArgumentParser()
    ap.add_argument('--batch', type=int, default=64)
    ap.add_argument('--iters', type=int, default=10)
    ap.add_argument('--pallas', action='store_true',
                    help='force the Pallas kernels (interpret mode on CPU '
                         '— correctness timing only, very slow)')
    ap.add_argument('--breakdown', action='store_true',
                    help='per-layer im2col/kernel timing + v5e roofline')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny CI run: batch 8, 2 iters, no file output '
                         'unless --out is given')
    ap.add_argument('--out', default=None)
    args = ap.parse_args()
    if args.smoke:
        args.batch, args.iters = min(args.batch, 8), min(args.iters, 2)
    out = args.out
    if out is None and not args.smoke:
        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), 'BENCH_serving.json')

    # Same auto-dispatch rule export_cnn applies for use_pallas=None, made
    # explicit here so the recorded label always matches the timed path.
    # On CPU the jnp path serves convs as fp32 lax.conv with export-folded
    # scales (no int8 conv units) — the CPU win is static scales + folded
    # dequant + the cheap depthwise lowering, not int8 compute.
    use_pallas = args.pallas or jax.default_backend() == 'tpu'
    x = jax.random.normal(jax.random.key(0), (args.batch, 32, 32, 3))
    fam = CNNFamily(SyntheticImages())
    results = {'backend': jax.default_backend(),
               'batch': args.batch,
               'int8_path': 'pallas' if use_pallas else 'jnp-ref',
               'configs': {}}

    for base in (RESNET8_CIFAR, VGG8_CIFAR, MOBILENET_SMALL_CIFAR):
        cfg = base.replace(w_bits=8, a_bits=8)
        params = init_cnn(jax.random.key(0), cfg)
        if base is RESNET8_CIFAR:      # early-exit serving entry
            params, cfg = fam.add_exits(jax.random.key(1), params,
                                        cfg.replace(exit_stages=()), (1,))
            cfg = cfg.replace(w_bits=8, a_bits=8)

        fake = jax.jit(lambda p, x, c=cfg: cnn_forward(p, c, x))
        us_fake = _time(fake, params, x, iters=args.iters)

        m = export_cnn(params, cfg, use_pallas=use_pallas)
        us_int8 = _time(m.fn, m.params, x, iters=args.iters)

        m_res = export_cnn(params, cfg, use_pallas=use_pallas, calibrate=x)
        us_res = _time(m_res.fn, m_res.params, x, iters=args.iters)

        entry = {'fakequant_fp32_us': round(us_fake, 1),
                 'exported_int8_us': round(us_int8, 1),
                 'int8_resident_us': round(us_res, 1),
                 'speedup': round(us_fake / us_int8, 3),
                 'resident_speedup': round(us_fake / us_res, 3),
                 'resident_vs_exported': round(us_int8 / us_res, 3),
                 'plan': m_res.summary()}
        if cfg.exit_stages:
            entry.update(_early_exit_entry(m, x, args.iters, threshold=0.85))

        # the 'fused' variant: the L-pass factored model, one-launch fused
        # kernel (forced) vs chained two-launch serving (same plan
        # otherwise), plus what the default cost model would actually ship
        fparams, _, mac_scale = fam.factorize(params, cfg, energy=0.6,
                                              min_rank=2)
        m_fused = export_cnn(fparams, cfg, use_pallas=use_pallas,
                             calibrate=x, select_kernels='fused')
        m_2l = export_cnn(fparams, cfg, use_pallas=use_pallas, calibrate=x,
                          fuse_lowrank=False)
        if m_fused.summary()['n_fused_lowrank'] > 0:
            m_sel = export_cnn(fparams, cfg, use_pallas=use_pallas,
                               calibrate=x)      # select_kernels='model'
            us_f = round(_time(m_fused.fn, m_fused.params, x,
                               iters=args.iters), 1)
            us_2 = round(_time(m_2l.fn, m_2l.params, x, iters=args.iters), 1)
            entry['fused'] = {
                'lowrank_mac_scale': round(mac_scale, 4),
                'n_fused_lowrank': m_fused.summary()['n_fused_lowrank'],
                'kernel_launches_fused':
                    m_fused.summary()['kernel_launches'],
                'kernel_launches_two_launch':
                    m_2l.summary()['kernel_launches'],
                'lowrank_fused_us': us_f,
                'lowrank_two_launch_us': us_2,
                'winner': 'fused' if us_f <= us_2 else 'chained',
                'model_selection': {
                    n: s['choice'] for n, s in
                    m_sel.summary()['lowrank_selection'].items()},
            }
            if args.smoke:
                # selection consistency: a measure-mode export must never
                # record a choice its own timings say is slower — the
                # launch-budget analyzer rule is the CI gate's version of
                # this contract, so the smoke shares it
                from repro.analysis import check
                m_meas = export_cnn(fparams, cfg, use_pallas=use_pallas,
                                    calibrate=x, select_kernels='measure')
                check(m_meas, x=x, rules=('launch-budget',), strict=True,
                      target=f'{cfg.name}:measure-smoke')
                entry['fused']['selection_consistent'] = True
                print(f'  smoke: measured selection consistent over '
                      f"{len(m_meas.summary()['lowrank_selection'])} layers")

        if args.smoke and 'mobilenet' in cfg.name:
            # the zero-fp32-MACs contract: depthwise serves on the int8
            # kernel, nothing falls back needlessly — int8-residency's
            # needless-fallback check is the rule-set version of the old
            # bespoke fallback==0 assert (mobilenet has no per-group
            # depth>1 convs, so any fallback is needless and errors)
            from repro.analysis import check
            check(m_res, x=x, rules=('int8-residency',), strict=True,
                  target=f'{cfg.name}:residency-smoke')
            s = entry['plan']
            assert s['n_depthwise'] > 0, s   # the kernel must actually run
            print(f"  smoke: mobilenet residency clean "
                  f"({s['n_depthwise']} depthwise layers on the int8 kernel)")

        if args.breakdown:
            print(f'{cfg.name} per-layer breakdown:')
            entry['layers'] = _breakdown(m_res, x, args.iters, use_pallas)
            # roofline over the plain serving path only — exit-head fc
            # layers are calibrated into the plan but fn never runs them
            # (LayerPlan.summary() splits them out the same way)
            entry['roofline_v5e'] = {
                k: (round(v, 9) if isinstance(v, float) else v)
                for k, v in int8_serving_roofline(
                    {n: e for n, e in m_res.plan.layers.items()
                     if not n.startswith('exit')}).items()}

        results['configs'][cfg.name] = entry
        print(f'{cfg.name}: fakequant_fp32={us_fake:.1f}us '
              f'exported_int8={us_int8:.1f}us '
              f'int8_resident={us_res:.1f}us '
              f'resident_vs_exported={us_int8 / us_res:.2f}x '
              f'(fallback MAC {entry["plan"]["fallback_mac_fraction"]:.1%})')

    if out:
        with open(out, 'w') as f:
            json.dump(results, f, indent=1)
        print(f'wrote {out}')


if __name__ == '__main__':
    main()
