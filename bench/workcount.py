"""Operations and bytes of the served CNN, counted from a configuration's
published shapes, and the table of chip peaks they are held against.

This is the benchmark's own arithmetic: it reads only the configuration
file, never the program's kernels, so it counts the same work whatever
implements it (a padded K or N inside a kernel is not counted).

The shapes come from the configuration's architecture keys, the same
keys the harness hands the program (:func:`layers` lists them).  Per
layer, for a batch of ``B`` images:

* ``ops``   = 2 * B * MACs (one multiply and one add per MAC; a 3x3
  depthwise conv over ``e`` channels has ``OH * OW * 9 * e`` MACs and
  ``9 * e`` weights);
* ``bytes`` = the int8 input plane + the int8 weights + the output
  (int8 for a conv, float32 logits for an fc).  The im2col patch matrix
  is not counted: it is the lowering's cost, not the layer's.

The least time of a layer is ``max(ops / int8 peak, bytes / HBM
bandwidth)``; a segment's is the sum over its layers.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(kind: str) -> dict:
    """The peak row for a ``device_kind``; a kind missing from
    ``peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, 'peaks.json')) as f:
        table = json.load(f)
    if kind not in table['devices']:
        raise KeyError(f'no published peaks for device kind {kind!r} in '
                       f'peaks.json')
    return table['devices'][kind]


def segment_of_stage(cfg, s: int) -> int:
    """Serving segment that runs stage ``s``: one segment per exit head,
    each ending at its head, and a last one to the final head."""
    return sum(1 for e in cfg['exit_stages'] if e < s)


def n_segments(cfg) -> int:
    return len(cfg['exit_stages']) + 1


def stage_strides(cfg) -> list[int]:
    """Stride of each stage's first block: ``stage_strides``, or else 2 at
    every stage after the first."""
    if 'stage_strides' in cfg:
        return list(cfg['stage_strides'])
    return [1] + [2] * (len(cfg['stage_blocks']) - 1)


def stage_expand(cfg) -> list[int]:
    """Inverted-residual expansion of each stage of a mobilenet:
    ``stage_expand``, or else ``expand_ratio`` for every stage; a file with
    neither is an error, never a default."""
    if 'stage_expand' in cfg:
        return list(cfg['stage_expand'])
    if 'expand_ratio' in cfg:
        return [cfg['expand_ratio']] * len(cfg['stage_blocks'])
    raise KeyError(f'mobilenet configuration {cfg.get("name")!r} states '
                   f'neither stage_expand nor expand_ratio')


def layers(cfg) -> list[dict]:
    """Every conv and fc of the served forward, per image, in order.

    Architecture keys read (the configuration file's, the same the
    program's ``CNNConfig`` gets): ``kind``, ``in_channels``,
    ``image_size``, ``stage_blocks``, ``stage_widths``, ``exit_stages``,
    ``num_classes``; and, where given, ``stage_strides`` (see
    :func:`stage_strides`), ``stem_width`` (the 3x3 stem's output, else
    ``stage_widths[0]``), ``head_width`` (a 1x1 ``head_conv`` after the
    last stage, ahead of the pool, whose output the final fc reads; absent:
    no such conv) and, for ``kind: "mobilenet"``, ``stage_expand`` /
    ``expand_ratio`` (see :func:`stage_expand`).

    A block is, by ``kind``: ``resnet`` ``conv1`` (3x3, the stage's
    stride), ``conv2`` (3x3) and a 1x1 ``proj`` where the shape changes;
    ``vgg`` ``conv1``; ``mobilenet`` an inverted residual: ``expand``
    (1x1 ``cin -> cin*t``, left out at ``t = 1``), ``dw`` (3x3 depthwise
    over the ``e`` expanded channels, the stage's stride) and ``project``
    (1x1 ``e -> w``).

    Each entry: ``name`` (the program's stable layer name), ``seg``,
    ``kind`` ('conv' | 'depthwise' | 'fc'), ``macs``, ``in_elems``,
    ``w_elems``, ``out_elems`` and ``out_bytes_per_elem``."""
    out = []
    hw = cfg['image_size']
    kind = cfg['kind']
    if kind not in ('resnet', 'vgg', 'mobilenet'):
        raise ValueError(f'no work count for kind {kind!r}')

    def conv(name, seg, hw_in, k, stride, ci, co, depthwise=False):
        hw_out = -(-hw_in // stride)
        per_pos = k * k * (1 if depthwise else ci) * co
        out.append({'name': name, 'seg': seg,
                    'kind': 'depthwise' if depthwise else 'conv',
                    'macs': hw_out * hw_out * per_pos,
                    'in_elems': hw_in * hw_in * ci,
                    'w_elems': per_pos,
                    'out_elems': hw_out * hw_out * co,
                    'out_bytes_per_elem': 1})
        return hw_out

    def fc(name, seg, din, dout):
        out.append({'name': name, 'seg': seg, 'kind': 'fc',
                    'macs': din * dout, 'in_elems': din,
                    'w_elems': din * dout, 'out_elems': dout,
                    'out_bytes_per_elem': 4})

    stem = cfg.get('stem_width', cfg['stage_widths'][0])
    hw = conv('stem', 0, hw, 3, 1, cfg['in_channels'], stem)
    cin = stem
    strides = stage_strides(cfg)
    expand = stage_expand(cfg) if kind == 'mobilenet' else None
    for s, (n, w) in enumerate(zip(cfg['stage_blocks'],
                                   cfg['stage_widths'])):
        seg = segment_of_stage(cfg, s)
        for b in range(n):
            stride = strides[s] if b == 0 else 1
            name, hw_in = f's{s}b{b}', hw
            if kind == 'mobilenet':
                e = cin * expand[s]
                if expand[s] != 1:
                    conv(f'{name}.expand', seg, hw, 1, 1, cin, e)
                hw = conv(f'{name}.dw', seg, hw, 3, stride, e, e,
                          depthwise=True)
                conv(f'{name}.project', seg, hw, 1, 1, e, w)
            else:
                hw = conv(f'{name}.conv1', seg, hw_in, 3, stride, cin, w)
                if kind == 'resnet':
                    conv(f'{name}.conv2', seg, hw, 3, 1, w, w)
                    if stride != 1 or cin != w:
                        conv(f'{name}.proj', seg, hw_in, 1, stride, cin, w)
            cin = w
        if s in cfg['exit_stages']:
            fc(f'exit{s}', seg, w, cfg['num_classes'])
    last = n_segments(cfg) - 1
    if 'head_width' in cfg:
        conv('head_conv', last, hw, 1, 1, cin, cfg['head_width'])
        cin = cfg['head_width']
    fc('head', last, cin, cfg['num_classes'])
    return out


def layer_ops_bytes(layer: dict, batch: int) -> tuple[float, float]:
    ops = 2.0 * batch * layer['macs']
    nbytes = (batch * layer['in_elems'] + layer['w_elems']
              + batch * layer['out_elems'] * layer['out_bytes_per_elem'])
    return ops, float(nbytes)


def segment_ops(cfg, seg: int, batch: int = 1) -> float:
    """Operations of segment ``seg`` for ``batch`` images."""
    return sum(layer_ops_bytes(lyr, batch)[0] for lyr in layers(cfg)
               if lyr['seg'] == seg)


def segment_least_s(cfg, seg: int, batch: int, peaks: dict) -> float:
    """The least time segment ``seg`` could take for ``batch`` images:
    the sum over its layers of the larger of the compute and the memory
    bound."""
    total = 0.0
    for lyr in layers(cfg):
        if lyr['seg'] != seg:
            continue
        ops, nbytes = layer_ops_bytes(lyr, batch)
        total += max(ops / peaks['int8_ops_per_s'],
                     nbytes / peaks['hbm_bytes_per_s'])
    return total


def segments_of_answer(cfg, exit_stage: int) -> int:
    """How many segments an answer that left at ``exit_stage`` ran
    (-1 = the final head: all of them)."""
    if exit_stage == -1:
        return n_segments(cfg)
    return segment_of_stage(cfg, exit_stage) + 1
