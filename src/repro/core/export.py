"""Export pass: compile a finished compression chain into an int8 serving
function running on the Pallas kernels.

``export_chain`` routes through a per-family serving-backend registry
(:func:`register_serving_backend`) — third-party families plug in serving
the same way third-party passes plug into core/registry.py.

The chain (e.g. D→P→L→Q→E over the registered passes, core/passes.py /
core/lowrank.py) ends with *fake-quant* params: every forward still runs
fp32 convs/matmuls and recomputes per-channel weight abs-max scales per
call.  This module realizes the Q pass at inference in two tiers:

1. **Dynamic-scale path** (``calibrate=None``, the PR-1 behavior):
   weights are snapshotted to int8 once (static per-out-channel scales) and
   activations get one dynamic per-tensor abs-max per layer — every layer
   reads/writes fp32 activations in HBM.
2. **Int8-resident path** (``calibrate=<sample batch>``): a *layer-plan
   compiler* runs one eager calibration forward over the sample batch,
   records a static activation scale for every layer boundary, and compiles
   a plan that picks, per layer:

   * the **fused low-rank kernel** (kernels/lowrank_conv.py) — a factored
     (u, v) conv pair in ONE Pallas launch, rank intermediate in VMEM —
     when the lane-padded rank fits a single 128 tile AND **cost-based
     kernel selection** picks it: the plan prices fused vs chained per
     layer (``select_kernels='model'`` via the analytic
     ``lowering_costs`` block-geometry model, ``'measure'`` by timing
     both lowerings at export) and records the winner + why in the plan,
     so a known-slower kernel never ships;
   * the **chained** int8 kernels (u then v, both int8-resident) when the
     rank exceeds the envelope or selection prefers two launches;
   * the plain int8 conv/matmul kernels with the **requantize epilogue**
     (kernels/quant_matmul.py ``out_scale``) for unfactored layers;
   * the **depthwise kernel** (kernels/depthwise_conv.py) for grouped
     convs with per-group depth 1 — direct per-channel int8 MACs,
     int8-in/int8-out, so MobileNet's ``fallback_mac_fraction`` is 0.
     Only per-group depth > 1 (absent from this repo's families) keeps
     the declared fp32 ``lax.conv`` fallback the summary reports.

   Activation scales are static Python floats baked into the jaxpr; no
   abs-max pass ever reads an activation tensor at serve time.  Between
   layers activations travel as int8 (``QAct``): conv kernels emit int8
   via the requantize epilogue, and the glue stage (GroupNorm + skip +
   ReLU, injected over models/cnn.py ``glue_fn``) dequantizes in-register
   and requantizes to the consumer's static scale — fp32 only appears at
   the final/exit logits and inside declared fallback layers.

3. **Batched early exit** — the E pass's exit heads are served batched:
   every sample takes its earliest confident exit (softmax confidence over
   a threshold), vectorized with where-masks instead of per-sample control
   flow.  ``export_chain`` threads the chain's calibrated
   ``exit_threshold`` into the served model.

On CPU (``use_pallas=None`` → auto) the serving function runs the pure-jnp
reference path: identical math and static scales, with dense layers on a
real int8 einsum but convs running a ``lax.conv`` whose operands are
dequantized in one fused XLA pass (CPU has no int8 conv units).  The
genuine int8 conv tiles are the TPU path (Mosaic-compiled Pallas kernels).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize_params_for_serving
from repro.kernels import ops, ref
from repro.kernels.depthwise_conv import fits_depthwise
from repro.kernels.lowrank_conv import fits_fused, lowering_costs
from repro.models import cnn as cnn_lib


def _serving_bits(cfg) -> tuple[int, int]:
    """(w_bits, a_bits) the int8 kernels run at: the chain's QAT bits when
    they fit in int8, else 8 (fp32/no-QAT models serve as W8A8).  Weights
    go down to bits=1 (DoReFa sign*mean, via quantize_weight); activation
    quantization needs >= 2 bits for a nonzero qmax."""
    w_bits = cfg.w_bits if 0 < cfg.w_bits <= 8 else 8
    a_bits = cfg.a_bits if 1 < cfg.a_bits <= 8 else 8
    return w_bits, a_bits


def _serving_layers(use_pallas: bool, a_bits: int):
    """Dynamic-scale int8 layer implementations injected into cnn_forward
    (the PR-1 exported path; cf. the int8-resident plan below).

    Weight scales live in the params pytree (static); quant here is the
    cfg hook tuple, ignored for weights — that is the QAT/serving split.
    Low-rank factored params ({'u','v'} pairs from family.factorize, each
    half already int8+scale after quantize_params_for_serving) chain two
    kernel calls, mirroring the QAT dispatch in models/cnn.py.
    """
    def conv_fn(p, x, *, stride=1, quant=(0, 0), groups=1, name=None):
        del quant, name
        if 'u' in p:
            h = conv_fn(p['u'], x, stride=stride, groups=groups)
            return conv_fn(p['v'], h)
        return ops.quant_conv_nhwc(x, p['w_q'], p['scale'], p.get('b'),
                                   stride=stride, groups=groups,
                                   a_bits=a_bits, use_pallas=use_pallas)

    def fc_fn(p, x, *, quant=(0, 0), name=None):
        del quant, name
        if 'u' in p:
            return fc_fn(p['v'], fc_fn(p['u'], x))
        y = ops.quant_dense(x, p['w_q'], p['scale'], a_bits=a_bits,
                            per_row=False, use_pallas=use_pallas)
        return y + p['b'] if 'b' in p else y

    return conv_fn, fc_fn


# ------------------------------------------------ int8-resident layer plan


@dataclass(frozen=True)
class QAct:
    """An int8 activation travelling between layers with its static scale.

    ``scale`` is a Python float captured at export calibration — a jaxpr
    constant, never recomputed at serve time.  HBM sees the int8 ``q``
    alone.  Registered as a pytree (``q`` the leaf, ``scale`` static aux
    data) so a stage-resumable serving segment can return its int8 carry
    across the jit boundary and the next segment can consume it — the
    scheduler moves int8 bytes between stages, never fp32.
    """
    q: Any
    scale: float

    @property
    def shape(self):
        return self.q.shape


jax.tree_util.register_pytree_node(
    QAct, lambda a: ((a.q,), a.scale), lambda s, c: QAct(c[0], s))


def _deq(x):
    """In-register dequantize (identity on tensors already fp32)."""
    if isinstance(x, QAct):
        return x.q.astype(jnp.float32) * x.scale
    return x


@dataclass
class LayerPlan:
    """The layer-plan compiler's output: per-layer static scales + kernel
    choice, keyed by the stable layer names models/cnn.py threads through
    cnn_forward.  ``layers`` covers convs/fcs, ``glues`` the inter-layer
    norm/act boundaries."""
    layers: dict
    glues: dict
    a_qmax: float

    def summary(self) -> dict:
        """Deployed-cost summary: MACs by kernel class, launch counts, the
        MAC fraction still served by the dequantized fp32 fallback (only
        per-group-depth>1 grouped convs — depthwise layers run the int8
        kernel, so mobilenet reports 0.0 here), and the per-layer fused-vs-
        chained low-rank selection with its reason, so a shipped kernel
        choice is always explicable.

        Counts cover the plain serving path (``ServingModel.fn``); the
        early-exit heads — calibrated too, but only executed by
        ``fn_exits`` — are reported separately as ``n_exit_heads`` /
        ``exit_head_launches``."""
        main = {n: e for n, e in self.layers.items()
                if not n.startswith('exit')}
        exits = {n: e for n, e in self.layers.items()
                 if n.startswith('exit')}
        total = sum(e['macs'] for e in main.values())
        fallback = sum(e['macs'] for e in main.values() if e['fallback'])
        return {
            'n_layers': len(main),
            'n_fused_lowrank': sum(1 for e in main.values()
                                   if e.get('fused')),
            'n_chained_lowrank': sum(1 for e in main.values()
                                     if e.get('factored')
                                     and not e.get('fused')),
            'n_depthwise': sum(1 for e in main.values()
                               if e.get('depthwise')),
            'n_fallback': sum(1 for e in main.values() if e['fallback']),
            'kernel_launches': sum(e['launches'] for e in main.values()),
            'n_exit_heads': len(exits),
            'exit_head_launches': sum(e['launches'] for e in exits.values()),
            'total_macs': total,
            'fallback_mac_fraction': fallback / max(total, 1),
            'lowrank_selection': {n: e['selection'] for n, e in main.items()
                                  if e.get('selection')},
            'lowering_cost_delta': self._lowering_cost_delta(main),
        }

    @staticmethod
    def _lowering_cost_delta(main) -> dict:
        """Measured-vs-modeled lowering costs for every layer that a
        measure-mode export timed (empty otherwise): how far off the
        analytic ``lowering_costs`` block model was from the wall clock,
        and whether both agree on the fused/chained winner — the feedback
        loop that keeps the roofline model honest."""
        out = {}
        for n, e in main.items():
            sel = e.get('selection') or {}
            if 'modeled_fused_us' not in sel or 'fused_us' not in sel:
                continue
            model_choice = ('fused' if sel['modeled_fused_us']
                            <= sel['modeled_chained_us'] else 'chained')
            out[n] = {
                'measured_fused_us': round(sel['fused_us'], 1),
                'measured_chained_us': round(sel['chained_us'], 1),
                'modeled_fused_us': round(sel['modeled_fused_us'], 1),
                'modeled_chained_us': round(sel['modeled_chained_us'], 1),
                'fused_measured_over_modeled': round(
                    sel['fused_us'] / max(sel['modeled_fused_us'], 1e-9), 3),
                'chained_measured_over_modeled': round(
                    sel['chained_us'] / max(sel['modeled_chained_us'],
                                            1e-9), 3),
                'model_agrees': model_choice == sel['choice'],
            }
        return out


def _compile_layer_plan(params, cfg, x, a_qmax, fuse_lowrank=True,
                        select_kernels='model') -> LayerPlan:
    """One eager calibration forward (the QAT fake-quant math) that records
    a static activation scale at every layer boundary and picks the serving
    kernel per layer (fused low-rank / chained / plain / depthwise /
    fallback).

    Factored pairs inside the fused envelope are priced fused-vs-chained:
    ``select_kernels='model'`` (default) uses the analytic
    ``lowering_costs`` block-geometry model at the calibration batch
    geometry; ``'fused'`` forces the one-launch lowering; ``'measure'`` is
    resolved afterwards by :func:`_measure_lowrank_selection` (wall-clock
    on the export backend).  ``fuse_lowrank=False`` forces the chained
    two-launch lowering regardless (the benchmark A/B).  The decision and
    its reason land in ``e['selection']`` and the plan summary."""
    layers, glues = {}, {}

    def amax(v) -> float:
        return max(float(jnp.max(jnp.abs(v))), 1e-8)

    def conv_fn(p, cx, *, stride=1, quant=(0, 0), groups=1, name=None):
        depthwise = groups > 1 and 'u' not in p and fits_depthwise(
            p['w'].shape)
        e = {'sx': amax(cx) / a_qmax, 'kind': 'conv',
             'fallback': groups > 1 and not depthwise,
             'depthwise': depthwise, 'factored': 'u' in p, 'fused': False,
             'stride': stride, 'in_shape': tuple(cx.shape),
             'groups': groups,
             'w_shape': None if 'u' in p else tuple(p['w'].shape)}
        if 'u' in p:
            mid = cnn_lib.conv(p['u'], cx, stride=stride, quant=quant,
                               groups=groups)
            y = cnn_lib.conv(p['v'], mid, quant=quant)
            e['h_scale'] = amax(mid) / a_qmax
            kh, kw, cin, r = p['u']['w'].shape
            cout = p['v']['w'].shape[-1]
            oh, ow = y.shape[1], y.shape[2]
            e['macs'] = oh * ow * r * (kh * kw * cin + cout)
            if not fits_fused(r, cout):
                sel = {'choice': 'chained',
                       'why': f'rank {r} exceeds the fused envelope'}
            elif not fuse_lowrank:
                sel = {'choice': 'chained',
                       'why': 'fuse_lowrank=False (forced two-launch A/B)'}
            elif select_kernels == 'fused':
                sel = {'choice': 'fused',
                       'why': 'select_kernels=fused (forced)'}
            else:   # 'model' now; 'measure' re-decides from wall-clock after
                c = lowering_costs(y.shape[0] * oh * ow, kh * kw * cin, r,
                                   cout)
                ch = 'fused' if c['fused_us'] <= c['chained_us'] else \
                    'chained'
                sel = {'choice': ch,
                       'why': (f"modeled fused {c['fused_us']:.1f}us vs "
                               f"chained {c['chained_us']:.1f}us"),
                       'fused_us': c['fused_us'],
                       'chained_us': c['chained_us']}
            e['selection'] = sel
            e['fused'] = sel['choice'] == 'fused'
            e['launches'] = 1 if e['fused'] else 2
            e['rank'] = r
            e['kernel'] = (kh, kw)
        else:
            y = cnn_lib.conv(p, cx, stride=stride, quant=quant, groups=groups)
            kh, kw, cin, cout = p['w'].shape
            oh, ow = y.shape[1], y.shape[2]
            e['macs'] = oh * ow * kh * kw * cin * cout
            e['launches'] = 0 if e['fallback'] else 1
            e['kernel'] = (kh, kw)
        e['out_scale'] = amax(y) / a_qmax
        e['out_shape'] = tuple(y.shape)
        layers[name] = e
        return y

    def fc_fn(p, cx, *, quant=(0, 0), name=None):
        e = {'sx': amax(cx) / a_qmax, 'kind': 'fc', 'fallback': False,
             'factored': 'u' in p, 'fused': False, 'out_scale': None,
             'in_shape': tuple(cx.shape)}
        if 'u' in p:
            mid = cnn_lib.fc(p['u'], cx, quant=quant)
            y = cnn_lib.fc(p['v'], mid, quant=quant)
            e['h_scale'] = amax(mid) / a_qmax
            din, r = p['u']['w'].shape
            e['macs'] = r * (din + p['v']['w'].shape[-1])
            e['launches'] = 2
        else:
            y = cnn_lib.fc(p, cx, quant=quant)
            e['macs'] = p['w'].shape[0] * p['w'].shape[1]
            e['launches'] = 1
        e['out_shape'] = tuple(y.shape)
        layers[name] = e
        return y

    def glue_fn(np_, y, *, act=None, skip=None, name=None):
        h = cnn_lib.norm_act(np_, y, act=act, skip=skip)
        glues[name] = amax(h) / a_qmax
        return h

    cnn_lib.cnn_forward(params, cfg, x, collect_exits=True, conv_fn=conv_fn,
                        fc_fn=fc_fn, glue_fn=glue_fn)
    return LayerPlan(layers=layers, glues=glues, a_qmax=a_qmax)


def _conv_f32(x, w, stride=1, groups=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), 'SAME', feature_group_count=groups,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _depthwise_shift_conv(x, w, stride=1):
    """Depthwise SAME conv as kh*kw shifted multiply-accumulates.

    XLA CPU lowers ``feature_group_count=C`` convs to a per-group loop
    that is ~20x slower than these C-wide elementwise FMAs; on the
    int8-resident CPU plan the declared depthwise fallback uses this
    instead.  x fp32 (B,H,W,C); w fp32 (KH,KW,1,C) — already
    scale-folded.  Value-identical to lax.conv (same pads, fp32 FMAs).
    """
    B, H, W, C = x.shape
    kh, kw = w.shape[0], w.shape[1]
    oh, ow = -(-H // stride), -(-W // stride)
    pad_h = max((oh - 1) * stride + kh - H, 0)
    pad_w = max((ow - 1) * stride + kw - W, 0)
    x = jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                    (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    y = None
    for i in range(kh):
        for j in range(kw):
            t = x[:, i:i + (oh - 1) * stride + 1:stride,
                  j:j + (ow - 1) * stride + 1:stride, :] * w[i, j, 0]
            y = t if y is None else y + t
    return y


def _fold_conv_consts(plan: LayerPlan, qparams):
    """Export-time constant folding for the jnp (CPU) backend.

    CPU convs run fp32 ``lax.conv`` regardless (no int8 conv units), so
    the dequant multiplies are hoisted out of the serve loop entirely:
    each conv's int8 weight is dequantized ONCE here and pre-scaled by the
    layer's *static* input scale — ``conv(x_q*sx, w_q*sw) ==
    conv(x_q, w_q*(sx*sw))`` by bilinearity.  At serve time the activation
    only pays an int8→fp32 cast.  Keyed by layer name; baked into the
    jaxpr as constants (the ``params`` argument keeps the int8 contract
    for storage/HBM accounting)."""
    fold = {}
    # resolve each plan layer's param subtree by its name path
    # (s0b1.conv2 -> stages[0][1]['conv2']) and pre-scale the weights
    for name, e in plan.layers.items():
        p = _resolve_layer_params(qparams, name)
        if e['kind'] != 'conv':
            continue
        if e['factored']:
            u, v = p['u'], p['v']
            fold[name] = {
                'u_w': u['w_q'].astype(jnp.float32) * u['scale'] * e['sx'],
                'u_b': u.get('b', 0.0),
                'v_w': v['w_q'].astype(jnp.float32) * v['scale']
                       * e['h_scale'],
                'v_b': v.get('b', 0.0),
            }
        else:
            fold[name] = {'w': p['w_q'].astype(jnp.float32) * p['scale']
                          * e['sx'],
                          'b': p.get('b', 0.0)}
    return fold


def _resolve_layer_params(params, name: str):
    """Map a stable layer name from models/cnn.py (``s0b1.conv2``,
    ``stem``, ``exit1``, ``head``) to its param subtree."""
    head = name.split('.')[0]
    if head == 'stem':
        return params['stem']
    if head == 'head':
        return params['head']
    if head.startswith('exit'):
        return params['exits'][head[4:]]
    s, b = head[1:].split('b')
    return params['stages'][int(s)][int(b)][name.split('.')[1]]


def _measure_lowrank_selection(plan: LayerPlan, qparams, use_pallas: bool,
                               *, reps: int = 3, tracer=None) -> None:
    """Resolve ``select_kernels='measure'``: wall-clock fused vs chained.

    For every factored conv inside the fused envelope, times both lowerings
    on the export backend (zero int8 input at the calibration geometry —
    timing is data-independent, best of ``reps`` after a compile warmup)
    and rewrites ``e['selection']`` / ``e['fused']`` with the measured
    winner, so the plan cannot ship a variant the machine just proved
    slower.  Mutates the plan in place.

    The modeled costs the analytic pricing produced survive as
    ``modeled_fused_us``/``modeled_chained_us`` in the rewritten selection
    (the summary's ``lowering_cost_delta`` block), and each timed launch
    lands on ``tracer`` as a wall-clock ``kernel.launch`` span — the spans
    ARE the measurement the decision is made from."""
    import time
    from repro.obs.trace import as_tracer
    tracer = as_tracer(tracer)
    qmax = plan.a_qmax
    for name, e in plan.layers.items():
        if e['kind'] != 'conv' or not e['factored']:
            continue
        if e['selection']['choice'] == 'chained' and 'envelope' in \
                e['selection']['why']:
            continue                     # rank-ineligible: nothing to race
        p = _resolve_layer_params(qparams, name)
        u, v = p['u'], p['v']
        bu = u.get('b', jnp.zeros(u['w_q'].shape[-1], jnp.float32))
        bv = v.get('b', jnp.zeros(v['w_q'].shape[-1], jnp.float32))
        xq = jnp.zeros(e['in_shape'], jnp.int8)

        def fused():
            return ops.lowrank_conv_nhwc(
                xq, u['w_q'], v['w_q'], u['scale'], v['scale'], bu, bv,
                sx=e['sx'], h_scale=e['h_scale'], stride=e['stride'],
                out_scale=e['out_scale'], h_qmax=qmax, out_qmax=qmax,
                use_pallas=use_pallas)

        def chained():
            h = ops.quant_conv_static(
                xq, u['w_q'], u['scale'], bu, sx=e['sx'], stride=e['stride'],
                out_scale=e['h_scale'], out_qmax=qmax, use_pallas=use_pallas)
            return ops.quant_conv_static(
                h, v['w_q'], v['scale'], bv, sx=e['h_scale'],
                out_scale=e['out_scale'], out_qmax=qmax,
                use_pallas=use_pallas)

        def best_us(f, variant):
            f().block_until_ready()      # compile outside the clock
            ts = []
            for rep in range(reps):
                w0 = tracer.now()        # no later than t0: spans never
                t0 = time.perf_counter()  # overlap the next rep's
                f().block_until_ready()
                us = (time.perf_counter() - t0) * 1e6
                tracer.add('kernel.launch', w0, w0 + us * 1e-6,
                           track='export', layer=name, variant=variant,
                           rep=rep, us=round(us, 1))
                ts.append(us)
            return min(ts)

        modeled = e['selection']          # the analytic pricing, pre-race
        tf = best_us(fused, 'fused')
        tc = best_us(chained, 'chained')
        e['selection'] = {'choice': 'fused' if tf <= tc else 'chained',
                          'why': (f'measured fused {tf:.0f}us vs chained '
                                  f'{tc:.0f}us'),
                          'fused_us': tf, 'chained_us': tc}
        if 'fused_us' in modeled:         # keep the model's claim on record
            e['selection']['modeled_fused_us'] = modeled['fused_us']
            e['selection']['modeled_chained_us'] = modeled['chained_us']
        e['fused'] = tf <= tc
        e['launches'] = 1 if e['fused'] else 2


def _resident_layers(plan: LayerPlan, use_pallas: bool, qparams=None):
    """Int8-resident layer implementations compiled from a LayerPlan.

    Pallas backend: convs consume/produce :class:`QAct` — int8 in HBM on
    static scales, requantize epilogues fused into the kernels, factored
    pairs in one launch when the rank fits.  The glue stage (GroupNorm +
    skip + activation) runs on the raw int8 codes (GroupNorm is invariant
    to the positive per-tensor scale, up to eps) and requantizes to its
    calibrated output scale — which by construction equals the consumer's
    input scale (both were recorded off the same tensor at calibration).

    jnp (CPU) backend: inter-layer tensors are the same int8 QActs, but
    inside a layer the conv carries fp32 (CPU has no int8 conv units, so
    an intra-layer int8 bounce would only add round-trips); all dequant
    multiplies are folded into export-time constants
    (:func:`_fold_conv_consts`), leaving one int8→fp32 cast per conv.

    Depthwise layers serve on the direct per-channel int8 kernel
    (kernels/depthwise_conv.py) on the Pallas backend — QAct in, QAct out,
    no fp32 in HBM — and on the scale-folded shift conv on CPU.  Only
    grouped convs with per-group depth > 1 remain the declared fp32
    fallback (QAct in, fp32 out, re-quantized by the next glue); none
    exist in this repo's families.
    """
    qmax = plan.a_qmax
    fold = None if use_pallas else _fold_conv_consts(plan, qparams)

    def as_qact(x, sx):
        if isinstance(x, QAct):
            return x
        return QAct(ref.requantize(x, sx, qmax), sx)

    def conv_fn(p, x, *, stride=1, quant=(0, 0), groups=1, name=None):
        del quant
        e = plan.layers[name]
        xq = as_qact(x, e['sx'])
        if e['fallback']:
            return ref.quant_conv_ref(xq.q, p['w_q'], xq.scale, p['scale'],
                                      p.get('b'), stride=stride,
                                      groups=groups)
        if not use_pallas:
            f = fold[name]
            xf = xq.q.astype(jnp.float32)
            if e.get('depthwise'):
                return _depthwise_shift_conv(xf, f['w'], stride) + f['b']
            if e['factored']:
                h = _conv_f32(xf, f['u_w'], stride) + f['u_b']
                h_q = ref.requantize(h, e['h_scale'], qmax)
                y = _conv_f32(h_q.astype(jnp.float32), f['v_w']) + f['v_b']
            else:
                y = _conv_f32(xf, f['w'], stride) + f['b']
            return y                     # fp32-carry to this layer's glue
        if e.get('depthwise'):
            y = ops.depthwise_conv_static(
                xq.q, p['w_q'], p['scale'], p.get('b'), sx=xq.scale,
                stride=stride, out_scale=e['out_scale'], out_qmax=qmax,
                use_pallas=True)
            return QAct(y, e['out_scale'])
        if e['factored']:
            u, v = p['u'], p['v']
            bu = u.get('b', jnp.zeros(u['w_q'].shape[-1], jnp.float32))
            bv = v.get('b', jnp.zeros(v['w_q'].shape[-1], jnp.float32))
            if e['fused']:
                y = ops.lowrank_conv_nhwc(
                    xq.q, u['w_q'], v['w_q'], u['scale'], v['scale'], bu, bv,
                    sx=xq.scale, h_scale=e['h_scale'], stride=stride,
                    out_scale=e['out_scale'], h_qmax=qmax, out_qmax=qmax,
                    use_pallas=True)
            else:
                h = ops.quant_conv_static(
                    xq.q, u['w_q'], u['scale'], bu, sx=xq.scale,
                    stride=stride, out_scale=e['h_scale'], out_qmax=qmax,
                    use_pallas=True)
                y = ops.quant_conv_static(
                    h, v['w_q'], v['scale'], bv, sx=e['h_scale'],
                    out_scale=e['out_scale'], out_qmax=qmax, use_pallas=True)
        else:
            y = ops.quant_conv_static(
                xq.q, p['w_q'], p['scale'], p.get('b'), sx=xq.scale,
                stride=stride, out_scale=e['out_scale'], out_qmax=qmax,
                use_pallas=True)
        return QAct(y, e['out_scale'])

    def fc_fn(p, x, *, quant=(0, 0), name=None):
        del quant
        e = plan.layers[name]
        xq = ref.requantize(_deq(x), e['sx'], qmax)
        if e['factored']:
            h = ops.quant_dense_static(
                xq, p['u']['w_q'], p['u']['scale'], p['u'].get('b'),
                sx=e['sx'], out_scale=e['h_scale'], out_qmax=qmax,
                use_pallas=use_pallas)
            return ops.quant_dense_static(
                h, p['v']['w_q'], p['v']['scale'], p['v'].get('b'),
                sx=e['h_scale'], use_pallas=use_pallas)
        return ops.quant_dense_static(xq, p['w_q'], p['scale'], p.get('b'),
                                      sx=e['sx'], use_pallas=use_pallas)

    def glue_fn(np_, y, *, act=None, skip=None, name=None):
        s = plan.glues[name]
        # GroupNorm is invariant to the input's positive per-tensor scale
        # (up to eps), so int8 inputs are normalized on their raw codes —
        # no dequantize multiply before the reduction.
        h = cnn_lib.group_norm(
            np_, y.q.astype(jnp.float32) if isinstance(y, QAct) else y)
        if skip is not None:
            h = h + _deq(skip)
        h = cnn_lib._ACTS[act](h)
        return QAct(ref.requantize(h, s, qmax), s)

    def pool_fn(h):
        if isinstance(h, QAct):           # scale the (B,C) mean, not the map
            return h.q.astype(jnp.float32).mean(axis=(1, 2)) * h.scale
        return h.mean(axis=(1, 2))

    return conv_fn, fc_fn, glue_fn, pool_fn


def _make_stage_fns(cfg, kw):
    """Split the compiled layer plan at the early-exit boundaries.

    Returns ``(stage_fns, stage_exits)``: one jit'd segment per exit
    boundary plus a final segment.  Segment ``i < last`` maps
    ``(params, carry) -> (exits, carry)`` where ``exits`` holds exactly the
    boundary head's logits and ``carry`` is whatever the injected glue
    produces at that stage boundary — an int8 :class:`QAct` on the
    int8-resident plan, fp32 on the dynamic path.  The final segment maps
    ``(params, carry) -> logits``.  ``stage_exits[i]`` names the exit
    stage segment ``i`` ends at (``None`` for the final segment).

    Chaining every segment is value-identical to the monolithic
    ``fn_exits`` — same layer names, same plan entries, same kernels — and
    bit-exact at fixed batch geometry; the request scheduler
    (repro/serving/) exploits the split to return exited samples after
    segment ``i`` and backfill their slots before paying for segment
    ``i + 1``.
    """
    bounds = tuple(sorted(cfg.exit_stages))
    fns, lo = [], 0
    for s in bounds:
        def seg(p, h, *, _lo=lo, _hi=s):
            return cnn_lib.cnn_forward(p, cfg, h, collect_exits=True,
                                       start_stage=_lo, stop_stage=_hi, **kw)
        fns.append(jax.jit(seg))
        lo = s + 1

    def final(p, h, *, _lo=lo):
        return cnn_lib.cnn_forward(p, cfg, h, start_stage=_lo, **kw)
    fns.append(jax.jit(final))
    return tuple(fns), bounds + (None,)


def exit_confidence(head_logits):
    """THE early-exit decision quantity: fp32 softmax max-confidence per
    sample.  Single definition shared by :func:`early_exit_batch`, the
    request scheduler (repro/serving/scheduler.py), and
    :func:`calibrate_exit_threshold` — a sample exits iff
    ``exit_confidence(head) > threshold``, strictly, everywhere."""
    return jax.nn.softmax(head_logits.astype(jnp.float32), axis=-1).max(-1)


def early_exit_batch(logits, exits, threshold):
    """Batched early-exit selection: (pred (B,), stage (B,) int32).

    Each sample takes the earliest exit whose :func:`exit_confidence`
    clears ``threshold``; stage is -1 for samples that ran to the final
    head.  Pure jnp (no per-sample control flow) so it jits into the
    serving fn.
    """
    pred = jnp.argmax(logits, -1)
    stage = jnp.full(pred.shape, -1, jnp.int32)
    taken = jnp.zeros(pred.shape, bool)
    for s in sorted(exits):
        take = (exit_confidence(exits[s]) > threshold) & ~taken
        pred = jnp.where(take, jnp.argmax(exits[s], -1), pred)
        stage = jnp.where(take, jnp.int32(s), stage)
        taken |= take
    return pred, stage


@dataclass
class ServingModel:
    """A compiled int8 serving endpoint for a compressed model."""
    cfg: Any
    params: Any                # int8 pytree: {'w_q', 'scale'(, 'b')} leaves
    fn: Callable               # jit'd (params, x) -> logits
    fn_exits: Callable | None = None   # jit'd (params, x) -> (logits, exits)
    plan: LayerPlan | None = None      # int8-resident exports only
    exit_threshold: float = 0.9        # E's operating point (export_chain)
    stage_fns: tuple | None = None     # layer plan split at exit boundaries
    stage_exits: tuple = ()            # exit stage each segment ends at
    backend: str = 'jnp'               # 'pallas' | 'jnp' serving lowering
    analysis: Any = None               # AnalysisReport from export verify=
    stage_devices: tuple = ()          # jax device pinned per segment
    stage_params: tuple | None = None  # params committed to stage_devices

    def serve(self, x):
        return self.fn(self.params, x)

    def serve_early_exit(self, x, threshold=None):
        """(pred, stage) per sample; requires exported exit heads.
        ``threshold=None`` uses the chain's calibrated operating point."""
        if self.fn_exits is None:
            raise ValueError('model was exported without exit heads')
        if x.shape[0] == 0:            # empty batch: nothing to run
            return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
        if threshold is None:
            threshold = self.exit_threshold
        logits, exits = self.fn_exits(self.params, x)
        return early_exit_batch(logits, exits, threshold)

    @property
    def n_stages(self) -> int:
        """Number of stage-resumable segments (0 = no exit heads)."""
        return len(self.stage_fns) if self.stage_fns else 0

    def run_stage(self, i: int, carry):
        """Run segment ``i`` of the stage-split plan.  ``carry`` is the
        input batch for ``i == 0``, else the carry segment ``i - 1``
        returned (int8 ``QAct`` on the resident plan).  Intermediate
        segments return ``(exits, carry)``; the last returns logits.
        On a placed model (:meth:`place_stages`) the segment reads the
        params copy committed to its device, so the computation runs
        where the placement put it."""
        if not self.stage_fns:
            raise ValueError('model was exported without exit heads '
                             '(no stage boundaries to resume at)')
        params = (self.stage_params[i] if self.stage_params is not None
                  else self.params)
        return self.stage_fns[i](params, carry)

    def place_stages(self, devices) -> 'ServingModel':
        """Pin segment ``k`` to ``devices[k]`` (one jax device per stage).

        Returns a NEW ServingModel whose ``stage_params[k]`` is the params
        pytree committed to ``devices[k]`` via ``jax.device_put`` (one
        transfer per *distinct* device — stages sharing a device share the
        copy).  Because committed operands pin where jit runs, every
        ``run_stage(k, ...)`` then executes on its assigned device; the
        compiled math is unchanged, so answers stay bit-exact with the
        unplaced model.  The int8 ``QAct`` carry between segments is NOT
        moved here — streaming it across stage boundaries is the
        scheduler's job (serving/placement.py)."""
        if not self.stage_fns:
            raise ValueError('model was exported without exit heads '
                             '(no stages to place)')
        devices = tuple(devices)
        if len(devices) != self.n_stages:
            raise ValueError(
                f'need one device per stage: got {len(devices)} devices '
                f'for {self.n_stages} stages')
        per_dev = {}
        for d in devices:
            if d not in per_dev:
                per_dev[d] = jax.device_put(self.params, d)
        return replace(self, stage_devices=devices,
                       stage_params=tuple(per_dev[d] for d in devices))

    def serve_stages(self, x):
        """Chain every stage segment: ``(logits, exits)``, value-identical
        to ``fn_exits(params, x)`` (the stage-split vs monolithic oracle)."""
        exits, h = {}, x
        for i in range(self.n_stages - 1):
            seg_exits, h = self.run_stage(i, h)
            exits.update(seg_exits)
        return self.run_stage(self.n_stages - 1, h), exits

    def summary(self) -> dict | None:
        """The layer plan's deployed-cost summary (int8-resident exports).
        Exports built with ``verify=`` carry their structured
        ``AnalysisReport`` under the ``analysis`` key."""
        if self.plan is None:
            return None
        s = self.plan.summary()
        if self.analysis is not None:
            s['analysis'] = self.analysis.to_dict()
        return s


def calibrate_exit_threshold(model: ServingModel, x, quantile=0.5):
    """Calibrate an early-exit operating point on a sample batch.

    Returns the confidence threshold at which a ``quantile`` fraction of
    the batch exits at its earliest head (0.5 -> the batch-median
    confidence).  Pure function: the caller decides where the value lives
    (``ChainState.exit_threshold`` via its setter, a benchmark record, a
    scheduler argument) — it must NOT be written into a live model behind
    the caller's back.
    """
    if model.fn_exits is None:
        raise ValueError('model was exported without exit heads')
    _, exits = model.fn_exits(model.params, x)
    conf = exit_confidence(exits[min(exits)])
    return float(jnp.quantile(conf, 1.0 - quantile)) - 1e-6


def export_cnn(params, cfg, *, use_pallas=None, calibrate=None,
               fuse_lowrank=True, select_kernels='model',
               verify=None, tracer=None) -> ServingModel:
    """Compile a (possibly chain-compressed) CNN to the int8 serving path.

    ``calibrate`` (a sample input batch) selects the int8-resident plan:
    static activation scales, requantize epilogues, and cost-selected
    low-rank lowerings — ``select_kernels='model'`` prices fused vs
    chained per factored layer with the analytic ``lowering_costs`` block
    model, ``'measure'`` races both lowerings on the export backend,
    ``'fused'`` forces the one-launch form (``fuse_lowrank=False`` forces
    chained, the benchmark A/B).  ``calibrate=None`` keeps the
    dynamic-scale path (one abs-max per layer per call, fp32 activations
    between layers).

    ``verify`` runs the static analyzer (repro/analysis) over the export:
    ``'strict'`` raises :class:`~repro.analysis.AnalysisError` on any
    error-severity finding, ``'warn'`` only records them.  Either way the
    structured ``AnalysisReport`` lands on ``model.analysis`` and in
    ``model.summary()['analysis']``.  ``None`` (default) skips analysis —
    exports on hot paths (per-test, per-benchmark-variant) stay cheap.

    ``tracer`` (a :class:`repro.obs.Tracer`) records the export timeline
    on the wall clock: an ``export.calibrate`` span around the layer-plan
    compile and, in measure mode, one ``kernel.launch`` span per timed
    lowering rep.
    """
    from repro.obs.trace import as_tracer
    tracer = as_tracer(tracer)
    if verify not in (None, 'strict', 'warn'):
        raise ValueError(f"verify must be None, 'strict' or 'warn', "
                         f'got {verify!r}')
    if use_pallas is None:
        use_pallas = jax.default_backend() == 'tpu'   # kernels are Mosaic-only
    w_bits, a_bits = _serving_bits(cfg)
    qparams = quantize_params_for_serving(params, bits=w_bits)
    plan = None
    if calibrate is not None:
        a_qmax = 2.0 ** (a_bits - 1) - 1.0
        with tracer.span('export.calibrate', track='export',
                         config=cfg.name, select_kernels=select_kernels,
                         batch=int(calibrate.shape[0])):
            plan = _compile_layer_plan(params, cfg, calibrate, a_qmax,
                                       fuse_lowrank=fuse_lowrank,
                                       select_kernels=select_kernels)
        if select_kernels == 'measure' and fuse_lowrank:
            _measure_lowrank_selection(plan, qparams, use_pallas,
                                       tracer=tracer)
        conv_fn, fc_fn, glue_fn, pool_fn = _resident_layers(
            plan, use_pallas, qparams=qparams)
        kw = dict(conv_fn=conv_fn, fc_fn=fc_fn, glue_fn=glue_fn,
                  pool_fn=pool_fn)
    else:
        conv_fn, fc_fn = _serving_layers(use_pallas, a_bits)
        kw = dict(conv_fn=conv_fn, fc_fn=fc_fn)

    @jax.jit
    def fn(p, x):
        return cnn_lib.cnn_forward(p, cfg, x, **kw)

    @jax.jit
    def fn_exits(p, x):
        return cnn_lib.cnn_forward(p, cfg, x, collect_exits=True, **kw)

    stage_fns, stage_exits = (None, ())
    if cfg.exit_stages:
        stage_fns, stage_exits = _make_stage_fns(cfg, kw)
    model = ServingModel(cfg=cfg, params=qparams, fn=fn,
                         fn_exits=fn_exits if cfg.exit_stages else None,
                         plan=plan, stage_fns=stage_fns,
                         stage_exits=stage_exits,
                         backend='pallas' if use_pallas else 'jnp')
    if verify is not None:
        from repro.analysis import check     # lazy: analysis imports core
        model.analysis = check(model, x=calibrate,
                               strict=(verify == 'strict'))
    return model


def export_lm(params, cfg) -> ServingModel:
    """Int8 export for the LM family: ``layers.dense`` consumes the
    {'w_q','scale'} form directly (in-register dequant; Pallas quant_matmul
    on TPU via the launch/steps serve step).  Exit-head serving for LMs
    stays with family.exit_logits."""
    from repro.models import transformer as tfm
    w_bits, _ = _serving_bits(cfg)
    qparams = quantize_params_for_serving(params, bits=w_bits)

    @jax.jit
    def fn(p, tokens):
        return tfm.forward(p, cfg, tokens)

    return ServingModel(cfg=cfg, params=qparams, fn=fn)


# ----------------------------------------------------- serving backends

# {family class: (state, use_pallas, calibrate) -> ServingModel}.  Third-
# party model families register here (mirroring the pass registry in
# core/registry.py) instead of core growing isinstance branches; lookup
# walks the MRO so subclassed families inherit their base family's backend.
_SERVING_BACKENDS: dict[type, Callable] = {}


def register_serving_backend(family_cls: type, backend: Callable) -> None:
    _SERVING_BACKENDS[family_cls] = backend


def serving_backend_for(family) -> Callable:
    for cls in type(family).__mro__:
        if cls in _SERVING_BACKENDS:
            return _SERVING_BACKENDS[cls]
    raise KeyError(
        f'no serving backend registered for family {type(family).__name__} '
        f'(registered: {sorted(c.__name__ for c in _SERVING_BACKENDS)}); '
        f'call export.register_serving_backend(FamilyCls, backend)')


def export_chain(state, *, use_pallas=None, calibrate=None) -> ServingModel:
    """Export a finished ChainState for serving via the family's registered
    backend.  ``calibrate`` (sample inputs) requests the int8-resident
    plan; the chain's E-pass operating point (``state.exit_threshold``)
    is threaded into the served model.

    Backends registered against the original two-arg ``(state,
    use_pallas)`` contract keep working: ``calibrate`` is only forwarded
    (as a keyword) to backends that declare it."""
    import inspect
    backend = serving_backend_for(state.family)
    sig = inspect.signature(backend).parameters
    takes_calibrate = 'calibrate' in sig or any(
        p.kind is p.VAR_KEYWORD for p in sig.values())
    if takes_calibrate:
        model = backend(state, use_pallas, calibrate=calibrate)
    elif calibrate is not None:
        raise TypeError(
            f'serving backend {backend!r} for {type(state.family).__name__} '
            f'does not accept calibrate= (int8-resident export); register '
            f'a backend with a (state, use_pallas, calibrate=None) '
            f'signature')
    else:
        model = backend(state, use_pallas)
    if getattr(state, 'exit_threshold', None) is not None:
        model.exit_threshold = state.exit_threshold
    return model


def _register_builtin_backends():
    from repro.core.family import CNNFamily, LMFamily
    register_serving_backend(
        CNNFamily, lambda state, use_pallas, calibrate=None: export_cnn(
            state.params, state.cfg, use_pallas=use_pallas,
            calibrate=calibrate))
    # the LM backend has no resident plan yet: it deliberately keeps the
    # two-arg signature so export_chain's calibrate guard raises instead of
    # silently ignoring a calibration batch
    register_serving_backend(
        LMFamily, lambda state, use_pallas: export_lm(state.params,
                                                      state.cfg))


_register_builtin_backends()
