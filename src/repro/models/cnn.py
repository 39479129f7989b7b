"""CIFAR-style CNN family (ResNet / VGG / MobileNetV2) — the paper's own
architectures, in functional JAX.

Notes vs. the paper: BatchNorm is replaced by GroupNorm(8) to keep the model
purely functional (no running stats in the training state) — this does not
interact with the compression-order findings, which are about D/P/Q/E
sequencing.  Every conv/fc routes through the same fake-quant hook as the
transformers (cfg.w_bits / cfg.a_bits), channel pruning physically shrinks
conv channels, and early-exit heads hang off stage boundaries
(cfg.exit_stages).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.quantization import (fake_quant_act_codes,
                                     fake_quant_weight_codes)
from repro.kernels.ref import quant_conv_ref


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    fan = kh * kw * cin
    return {'w': jax.random.normal(key, (kh, kw, cin, cout), dtype)
            * math.sqrt(2.0 / fan),
            'b': jnp.zeros((cout,), dtype)}


def conv(p, x, *, stride=1, quant=(0, 0), groups=1, name=None):
    """QAT/fp32 conv: per-call fake-quant hooks on weight and activation.

    This is the *training* path.  The serving path (core/export.py) swaps
    this out via cnn_forward's ``conv_fn`` for an int8 Pallas conv with
    static, export-time weight scales.  ``name`` is the stable layer name
    cnn_forward threads through (ignored here; the export layer-plan
    compiler keys its static-scale plan by it).

    A low-rank-factored conv (core/family.py factorize: {'u': spatial conv
    to rank r, 'v': 1x1 conv back up}) chains the two sub-convs; each gets
    its own fake-quant hooks, matching the exported int8 path.
    """
    del name
    if 'u' in p:
        h = conv(p['u'], x, stride=stride, quant=quant, groups=groups)
        return conv(p['v'], h, quant=quant)
    # the int8 kernels' order: convolve the integer codes, then scale by
    # (sx * sw) -- dequantizing first is the same math but rounds
    # differently, and the next layer's requantize can turn that last ulp
    # into flipped codes between training and serving.  The codes conv is
    # exact only below K = 1024 (ref._int_conv); above it fp32 partial
    # sums round, so training tracks serving closely but not bit for bit.
    # An operand that is not quantized rides with scale 1, so fp32
    # training is a plain conv.
    wc, sw = fake_quant_weight_codes(p['w'], quant[0])
    xc, sx = fake_quant_act_codes(x, quant[1])
    return quant_conv_ref(xc, wc, sx, sw.reshape(-1), p['b'], stride=stride,
                          groups=groups)


def out_channels(p) -> int:
    """Output channels of a conv/fc param dict (fp32 'w', int8 'w_q', or
    low-rank factored {'u','v'} — the 'v' half carries the output dim)."""
    if 'v' in p and 'w' not in p and 'w_q' not in p:
        return out_channels(p['v'])
    return (p['w'] if 'w' in p else p['w_q']).shape[-1]


def group_norm(p, x, groups=8, eps=1e-5):
    B, H, W, C = x.shape
    g = math.gcd(groups, C)
    xg = x.reshape(B, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    x = xg.reshape(B, H, W, C)
    return x * p['scale'] + p['bias']


def _norm_init(c, dtype=jnp.float32):
    return {'scale': jnp.ones((c,), dtype), 'bias': jnp.zeros((c,), dtype)}


def _fc_init(key, din, dout, dtype=jnp.float32):
    return {'w': jax.random.normal(key, (din, dout), dtype)
            * math.sqrt(1.0 / din),
            'b': jnp.zeros((dout,), dtype)}


def fc(p, x, *, quant=(0, 0), name=None):
    del name
    if 'u' in p:                   # low-rank factored: two chained matmuls
        return fc(p['v'], fc(p['u'], x, quant=quant), quant=quant)
    wc, sw = fake_quant_weight_codes(p['w'], quant[0])
    xc, sx = fake_quant_act_codes(x, quant[1])
    y = (xc @ wc) * (sx * sw.reshape(-1))        # conv's order, as served
    return y + p['b'] if 'b' in p else y


# ------------------------------------------------------------------------ init


def init_cnn(key, cfg):
    ks = iter(jax.random.split(key, 4096))
    p = {'stem': _conv_init(next(ks), 3, 3, cfg.in_channels,
                            cfg.stage_widths[0]),
         'stem_norm': _norm_init(cfg.stage_widths[0])}
    stages = []
    cin = cfg.stage_widths[0]
    for s, (n, w) in enumerate(zip(cfg.stage_blocks, cfg.stage_widths)):
        blocks = []
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            if cfg.kind == 'resnet':
                blk = {'conv1': _conv_init(next(ks), 3, 3, cin, w),
                       'n1': _norm_init(w),
                       'conv2': _conv_init(next(ks), 3, 3, w, w),
                       'n2': _norm_init(w)}
                if stride != 1 or cin != w:
                    blk['proj'] = _conv_init(next(ks), 1, 1, cin, w)
            elif cfg.kind == 'vgg':
                blk = {'conv1': _conv_init(next(ks), 3, 3, cin, w),
                       'n1': _norm_init(w)}
            else:  # mobilenet inverted residual
                e = cin * cfg.expand_ratio
                blk = {'expand': _conv_init(next(ks), 1, 1, cin, e),
                       'n1': _norm_init(e),
                       'dw': _conv_init(next(ks), 3, 3, 1, e),
                       'n2': _norm_init(e),
                       'project': _conv_init(next(ks), 1, 1, e, w),
                       'n3': _norm_init(w)}
            blocks.append(blk)
            cin = w
        stages.append(blocks)
    p['stages'] = stages
    p['head'] = _fc_init(next(ks), cin, cfg.num_classes)
    if cfg.exit_stages:
        p['exits'] = {str(s): _fc_init(next(ks), cfg.stage_widths[s],
                                       cfg.num_classes)
                      for s in cfg.exit_stages}
    return p


# -------------------------------------------------------------------- forward


_ACTS = {None: lambda x: x, 'relu': jax.nn.relu, 'relu6': jax.nn.relu6}


def norm_act(p, y, *, act=None, skip=None, name=None):
    """The inter-layer glue: GroupNorm -> (+skip) -> activation, fp32.

    Every tensor that travels between conv layers goes through exactly one
    ``glue_fn`` call — which is why core/export.py can swap this for an
    int8-resident version (dequantize in-register, norm/act in fp32
    registers, requantize to the next layer's static scale) and know that
    no activation reaches HBM in fp32.  ``name`` keys the export plan.
    """
    del name
    h = group_norm(p, y)
    if skip is not None:
        h = h + skip
    return _ACTS[act](h)


def global_pool(x):
    """Global average pool (B,H,W,C) -> (B,C) ahead of fc/exit heads."""
    return x.mean(axis=(1, 2))


def _scoped(fn, name, *args, **kw):
    """One injected layer call under ``jax.named_scope(name)``, so the
    device ops it lowers to carry the layer's stable name in their
    metadata (a profile charges them to the layer).  Metadata only: the
    computation is unchanged."""
    with jax.named_scope(name):
        return fn(*args, name=name, **kw)


def _block_forward(blk, x, kind, stride, quant, conv_fn, glue_fn,
                   name=''):
    if kind == 'resnet':
        h = _scoped(glue_fn, f'{name}.n1', blk['n1'],
                    _scoped(conv_fn, f'{name}.conv1', blk['conv1'], x,
                            stride=stride, quant=quant),
                    act='relu')
        y = _scoped(conv_fn, f'{name}.conv2', blk['conv2'], h, quant=quant)
        skip = _scoped(conv_fn, f'{name}.proj', blk['proj'], x,
                       stride=stride, quant=quant) if 'proj' in blk else x
        return _scoped(glue_fn, f'{name}.n2', blk['n2'], y, act='relu',
                       skip=skip)
    if kind == 'vgg':
        return _scoped(glue_fn, f'{name}.n1', blk['n1'],
                       _scoped(conv_fn, f'{name}.conv1', blk['conv1'], x,
                               stride=stride, quant=quant),
                       act='relu')
    # mobilenet
    e = out_channels(blk['expand'])
    h = _scoped(glue_fn, f'{name}.n1', blk['n1'],
                _scoped(conv_fn, f'{name}.expand', blk['expand'], x,
                        quant=quant),
                act='relu6')
    h = _scoped(glue_fn, f'{name}.n2', blk['n2'],
                _scoped(conv_fn, f'{name}.dw', blk['dw'], h, stride=stride,
                        quant=quant, groups=e),
                act='relu6')
    skip = x if (stride == 1
                 and x.shape[-1] == out_channels(blk['project'])) else None
    return _scoped(glue_fn, f'{name}.n3', blk['n3'],
                   _scoped(conv_fn, f'{name}.project', blk['project'], h,
                           quant=quant),
                   skip=skip)


def cnn_forward(params, cfg, x, *, collect_exits=False, conv_fn=None,
                fc_fn=None, glue_fn=None, pool_fn=None, start_stage=0,
                stop_stage=None):
    """x: (B, H, W, C) -> logits (B, classes); optionally exit logits dict.

    ``conv_fn``/``fc_fn``/``glue_fn``/``pool_fn`` inject the layer
    implementations: the default is the QAT fake-quant path
    (:func:`conv`/:func:`fc`/:func:`norm_act`/:func:`global_pool`);
    core/export.py injects int8 serving layers over the same topology, so
    training and serving cannot drift structurally.  Each call site carries
    a stable ``name`` (``s{stage}b{block}.conv1`` etc.) so the export
    layer-plan compiler can attach per-layer static activation scales, and
    runs under ``jax.named_scope`` of that name (the pools under
    ``exit{s}.pool`` / ``head.pool``), so a device profile charges each
    op to its layer.

    ``start_stage``/``stop_stage`` make the forward *stage-resumable* (the
    serving scheduler's continuous-batching split, core/export.py
    ``_make_stage_fns``):

    * ``start_stage=0`` runs the stem; ``start_stage=s > 0`` treats ``x``
      as the carry activation that left stage ``s - 1`` (whatever type the
      injected glue produced there — fp32 in QAT, an int8 ``QAct`` on the
      int8-resident plan) and skips the stem and earlier stages.
    * ``stop_stage=s`` stops after stage ``s`` and returns ``(exits, h)``
      — the exit logits collected in range plus the carry — WITHOUT running
      the final head.  ``stop_stage=None`` runs to the head as before.

    Layer names are position-stable, so a resumed segment reads the same
    export-plan entries the monolithic forward calibrated.
    """
    conv_fn = conv_fn or conv
    fc_fn = fc_fn or fc
    glue_fn = glue_fn or norm_act
    pool_fn = pool_fn or global_pool
    quant = (cfg.w_bits, cfg.a_bits)
    if start_stage == 0:
        h = _scoped(glue_fn, 'stem.norm', params['stem_norm'],
                    _scoped(conv_fn, 'stem', params['stem'], x,
                            quant=quant),
                    act='relu')
    else:
        h = x                                     # carry from stage s-1
    exits = {}
    for s, blocks in enumerate(params['stages']):
        if s < start_stage:
            continue
        if stop_stage is not None and s > stop_stage:
            break
        for b, blk in enumerate(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            h = _block_forward(blk, h, cfg.kind, stride, quant, conv_fn,
                               glue_fn, name=f's{s}b{b}')
        if collect_exits and 'exits' in params and str(s) in params['exits']:
            with jax.named_scope(f'exit{s}.pool'):
                feat = pool_fn(h)
            exits[s] = _scoped(fc_fn, f'exit{s}', params['exits'][str(s)],
                               feat, quant=quant)
    if stop_stage is not None:
        return exits, h                           # mid-network segment
    with jax.named_scope('head.pool'):
        feat = pool_fn(h)
    logits = _scoped(fc_fn, 'head', params['head'], feat, quant=quant)
    if collect_exits:
        return logits, exits
    return logits
