"""Plain float32 reference of the CIFAR-form ResNet and VGG classifiers.

This file is the benchmark's yardstick for both configurations whose
``"reference"`` names it.  It imports nothing of the system under test.

* :func:`init` makes random weights from a key, in one jitted call on the
  device, in the parameter layout the served program reads: HWIO conv
  kernels ``{'w', 'b'}``, GroupNorm ``{'scale', 'bias'}``, fc ``{'w', 'b'}``,
  ``params['stages'][s][b]`` blocks, exit heads under ``params['exits']``
  keyed by the stage number as a string.  Biases and norm affines are drawn
  too (small, not zero), so a served path that dropped one would show.
* :func:`forward` is the straightforward forward pass at
  ``Precision.HIGHEST``: conv, GroupNorm(8), ReLU, residual add, global
  average pool, fc.  It returns the final logits and the logits of every
  exit head.

Departures from the papers, shared with the served program and listed in
each configuration file: GroupNorm with 8 groups in place of BatchNorm; a
3x3 stride-1 stem for 32x32 inputs; the ResNet projection shortcut is a 1x1
conv without a norm; VGG downsamples with a stride-2 first conv of each
stage after the first in place of max-pool, and classifies with global
average pool and one fc in place of the three fc layers; early-exit heads
(global pool then fc) after the stages the configuration names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_GROUPS = 8
NORM_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def _conv_params(key, kh, kw, cin, cout):
    kw_, kb = jax.random.split(key)
    return {'w': jax.random.normal(kw_, (kh, kw, cin, cout), jnp.float32)
            * math.sqrt(2.0 / (kh * kw * cin)),
            'b': 0.05 * jax.random.normal(kb, (cout,), jnp.float32)}


def _norm_params(key, c):
    ks, kb = jax.random.split(key)
    return {'scale': 1.0 + 0.1 * jax.random.normal(ks, (c,), jnp.float32),
            'bias': 0.1 * jax.random.normal(kb, (c,), jnp.float32)}


def _fc_params(key, din, dout):
    kw_, kb = jax.random.split(key)
    return {'w': jax.random.normal(kw_, (din, dout), jnp.float32)
            * math.sqrt(1.0 / din),
            'b': 0.05 * jax.random.normal(kb, (dout,), jnp.float32)}


def init(key, cfg):
    """Random weights for ``cfg`` (a configuration file's dict)."""
    counter = iter(range(1 << 20))

    def nxt():
        return jax.random.fold_in(key, next(counter))

    widths, blocks = cfg['stage_widths'], cfg['stage_blocks']
    p = {'stem': _conv_params(nxt(), 3, 3, cfg['in_channels'], widths[0]),
         'stem_norm': _norm_params(nxt(), widths[0])}
    stages, cin = [], widths[0]
    for s, (n, w) in enumerate(zip(blocks, widths)):
        stage = []
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            blk = {'conv1': _conv_params(nxt(), 3, 3, cin, w),
                   'n1': _norm_params(nxt(), w)}
            if cfg['kind'] == 'resnet':
                blk['conv2'] = _conv_params(nxt(), 3, 3, w, w)
                blk['n2'] = _norm_params(nxt(), w)
                if stride != 1 or cin != w:
                    blk['proj'] = _conv_params(nxt(), 1, 1, cin, w)
            elif cfg['kind'] != 'vgg':
                raise ValueError(f"no reference for kind {cfg['kind']!r}")
            stage.append(blk)
            cin = w
        stages.append(stage)
    p['stages'] = stages
    p['head'] = _fc_params(nxt(), cin, cfg['num_classes'])
    p['exits'] = {str(s): _fc_params(nxt(), widths[s], cfg['num_classes'])
                  for s in cfg['exit_stages']}
    return p


def _conv(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p['w'], (stride, stride), 'SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)
    return y + p['b']


def _group_norm(p, x):
    b, h, w, c = x.shape
    g = math.gcd(NORM_GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + NORM_EPS)
    return xg.reshape(b, h, w, c) * p['scale'] + p['bias']


def _fc(p, x):
    return jnp.dot(x, p['w'], precision=HIGHEST) + p['b']


def forward(params, cfg, x):
    """x float32 (B, H, W, C) -> (final logits (B, classes),
    {exit stage: exit logits (B, classes)})."""
    relu = jax.nn.relu
    h = relu(_group_norm(params['stem_norm'], _conv(params['stem'], x, 1)))
    exits = {}
    for s, stage in enumerate(params['stages']):
        for b, blk in enumerate(stage):
            stride = 2 if (b == 0 and s > 0) else 1
            y = relu(_group_norm(blk['n1'], _conv(blk['conv1'], h, stride)))
            if cfg['kind'] == 'resnet':
                y = _group_norm(blk['n2'], _conv(blk['conv2'], y, 1))
                skip = _conv(blk['proj'], h, stride) if 'proj' in blk else h
                y = relu(y + skip)
            h = y
        if str(s) in params['exits']:
            exits[s] = _fc(params['exits'][str(s)], h.mean(axis=(1, 2)))
    return _fc(params['head'], h.mean(axis=(1, 2))), exits
