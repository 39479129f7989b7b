"""Operations and bytes of the served CNN, counted from a configuration's
published shapes, and the table of chip peaks they are held against.

This is the benchmark's own arithmetic: it reads only the configuration
file, never the program's kernels, so it counts the same work whatever
implements it (a padded K or N inside a kernel is not counted).

Per layer, for a batch of ``B`` images:

* ``ops``   = 2 * B * MACs (one multiply and one add per MAC);
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


def layers(cfg) -> list[dict]:
    """Every conv and fc of the served forward, per image, in order.

    Each entry: ``name`` (the program's stable layer name), ``seg``,
    ``kind`` ('conv' | 'fc'), ``macs``, ``in_elems``, ``w_elems``,
    ``out_elems`` and ``out_bytes_per_elem``."""
    out = []
    hw = cfg['image_size']
    cin = cfg['in_channels']

    def conv(name, seg, hw_in, k, stride, ci, co):
        hw_out = -(-hw_in // stride)
        out.append({'name': name, 'seg': seg, 'kind': 'conv',
                    'macs': hw_out * hw_out * k * k * ci * co,
                    'in_elems': hw_in * hw_in * ci,
                    'w_elems': k * k * ci * co,
                    'out_elems': hw_out * hw_out * co,
                    'out_bytes_per_elem': 1})
        return hw_out

    def fc(name, seg, din, dout):
        out.append({'name': name, 'seg': seg, 'kind': 'fc',
                    'macs': din * dout, 'in_elems': din,
                    'w_elems': din * dout, 'out_elems': dout,
                    'out_bytes_per_elem': 4})

    w0 = cfg['stage_widths'][0]
    hw = conv('stem', 0, hw, 3, 1, cin, w0)
    cin = w0
    for s, (n, w) in enumerate(zip(cfg['stage_blocks'],
                                   cfg['stage_widths'])):
        seg = segment_of_stage(cfg, s)
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            hw_in = hw
            hw = conv(f's{s}b{b}.conv1', seg, hw_in, 3, stride, cin, w)
            if cfg['kind'] == 'resnet':
                conv(f's{s}b{b}.conv2', seg, hw, 3, 1, w, w)
                if stride != 1 or cin != w:
                    conv(f's{s}b{b}.proj', seg, hw_in, 1, stride, cin, w)
            cin = w
        if s in cfg['exit_stages']:
            fc(f'exit{s}', seg, w, cfg['num_classes'])
    fc('head', n_segments(cfg) - 1, cin, cfg['num_classes'])
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
