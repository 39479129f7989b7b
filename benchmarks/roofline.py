"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) cell:
    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = 2 x HLO_buffer_bytes_per_device / HBM_bw   (r+w proxy)
    collective term = collective_bytes_per_device / ICI_link_bw
plus the dominant term, MODEL_FLOPS (6ND / 2ND), and the
MODEL_FLOPS / HLO_FLOPs usefulness ratio (catches remat/redundancy waste).

Hardware constants: the TPU v5e row of the one sourced peak table,
``repro.kernels.tiling.DEVICE_PEAKS`` (197 TFLOP/s bf16, 394 TOP/s int8,
819 GB/s HBM, ~50 GB/s/link ICI).  Everything here is a modelled v5e
prediction, never a device measurement.  Methodology notes: the per-device
numbers come from the CPU-backend SPMD module (bf16 dots promoted to f32 ->
bytes are an upper bound; see launch/hlo_analysis.py docstring).

Usage: PYTHONPATH=src python -m benchmarks.roofline [--mesh pod]
"""
from __future__ import annotations

import argparse
import json
import os

from repro.kernels.tiling import DEVICE_PEAKS, MODELLED_KIND

V5E = DEVICE_PEAKS[MODELLED_KIND]
PEAK_FLOPS = V5E['bf16_flops']
HBM_BW = V5E['hbm_bytes_per_s']
ICI_BW = V5E['ici_link_bytes_per_s']
HBM_PER_CHIP = V5E['hbm_bytes']
INT8_PEAK_FLOPS = V5E['int8_ops']    # MXU: int8 doubles bf16 MACs/cycle


def int8_serving_roofline(plan_layers: dict) -> dict:
    """Roofline terms for one exported-CNN serving step on v5e, from a
    core/export.py LayerPlan's layer dicts (shapes include the batch).

    Two memory models per step: the PR-1 exported path (fp32 activations
    between layers + one abs-max read per layer) vs the int8-resident path
    (activations int8 in HBM, no abs-max pass).  This is what the
    requantize-epilogue work actually moves: the compute term is identical,
    the activation-traffic term shrinks ~4x — the fp32 HBM floor that
    bounded every previous speedup.

    The int8-resident term is dtype-accurate per layer: only the declared
    fp32 fallback layers (per-group depth > 1 grouped convs, none in this
    repo's families) pay 4 bytes/element on their outputs — depthwise
    layers run the int8 kernel (kernels/depthwise_conv.py) and move int8
    like everything else, with their share reported separately
    (``depthwise_bytes`` / ``depthwise_traffic_fraction``) instead of
    hiding in a fallback bucket.
    """
    # byte accounting is shared with the static analyzer's hlo-traffic
    # rule (repro/analysis/traffic.py) — one implementation, enforced at
    # export AND reported here
    from repro.analysis.traffic import boundary_bytes
    bb = boundary_bytes(plan_layers)
    elems_in, elems_out = bb['elems_in'], bb['elems_out']
    macs = sum(e['macs'] for e in plan_layers.values())
    batch = next(iter(plan_layers.values()))['in_shape'][0]
    flops = 2.0 * macs * batch
    t_c = flops / INT8_PEAK_FLOPS
    # fp32 path: read + write each layer boundary in fp32, plus the
    # dynamic abs-max pass re-reading every layer input
    t_m_fp32 = (4.0 * elems_in + 4.0 * elems_out + 4.0 * elems_in) / HBM_BW
    int8_bytes, dw_bytes = bb['int8_bytes'], bb['depthwise_bytes']
    t_m_int8 = int8_bytes / HBM_BW
    return {
        'compute_s': t_c,
        'memory_s_fp32_roundtrip': t_m_fp32,
        'memory_s_int8_resident': t_m_int8,
        'depthwise_bytes': dw_bytes,    # per step; shapes include the batch
        'depthwise_traffic_fraction': dw_bytes / max(int8_bytes, 1e-30),
        'bound_fp32': 'memory' if t_m_fp32 > t_c else 'compute',
        'bound_int8': 'memory' if t_m_int8 > t_c else 'compute',
        'traffic_reduction': t_m_fp32 / max(t_m_int8, 1e-30),
    }


def _prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n

SHAPE_TOKENS = {'train_4k': (256, 4096, 'train'),
                'prefill_32k': (32, 32768, 'prefill'),
                'decode_32k': (128, 32768, 'decode'),
                'long_500k': (1, 524288, 'decode')}


def active_param_count(cfg):
    """N (active) from abstract shapes; MoE routed experts scaled by
    (top_k/ n_experts); embedding table excluded, unembed matmul included."""
    import jax
    from repro.models import build_model
    p = jax.eval_shape(lambda: build_model(cfg).init(jax.random.key(0)))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(p)[0]:
        keys = [str(getattr(q, 'key', getattr(q, 'idx', q))) for q in path]
        n = 1
        for d in leaf.shape:
            n *= d
        if 'embed' in keys and 'exit' not in keys:
            if 'unembed' in keys:
                total += n
            continue                       # lookup, not matmul
        if 'moe' in keys and keys[-1] in ('wi', 'wg', 'wo'):
            E = cfg.n_experts
            n = n * cfg.top_k / E
        total += int(n)
    if cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model      # unembed matmul reuse
    return total


def model_flops(cfg, shape):
    B, S, kind = SHAPE_TOKENS[shape]
    N = active_param_count(cfg)
    if kind == 'train':
        return 6.0 * N * B * S
    if kind == 'prefill':
        return 2.0 * N * B * S
    return 2.0 * N * B                      # decode: one token per sequence


def analyze_cell(path, cfg_cache):
    from repro.configs import get_config
    with open(path) as f:
        r = json.load(f)
    cfg = cfg_cache.setdefault(r['arch'], get_config(r['arch']))
    chips = r['devices']
    t_c = r['flops_per_device'] / PEAK_FLOPS
    # memory term: intermediate buffers (written+read) + argument reads
    # (params + caches — the dtype-accurate memory_analysis numbers; this is
    # what the int8-serving iteration moves)
    t_m = (2.0 * r['bytes_per_device']
           + r['memory']['argument_bytes']) / HBM_BW
    coll = sum(r['collective_bytes'].values())
    t_x = coll / ICI_BW
    dom = max((('compute', t_c), ('memory', t_m), ('collective', t_x)),
              key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, r['shape'])
    hlo_global = r['flops_per_device'] * chips
    mem = r['memory']
    hbm_need = mem['argument_bytes'] + mem['temp_bytes'] \
        + mem['output_bytes'] - mem.get('alias_bytes', 0)
    return {
        'arch': r['arch'], 'shape': r['shape'], 'mesh': r['mesh'],
        'chips': chips,
        'compute_s': t_c, 'memory_s': t_m, 'collective_s': t_x,
        'dominant': dom,
        'model_flops': mf, 'hlo_flops_global': hlo_global,
        'useful_ratio': mf / hlo_global if hlo_global else 0.0,
        'hbm_bytes_per_device': hbm_need,
        'fits_hbm': hbm_need <= HBM_PER_CHIP,
        'collective_by_kind': r['collective_bytes'],
        'compile_s': r.get('compile_s'),
    }


def main(mesh='pod', out_dir='experiments/dryrun'):
    d = os.path.join(out_dir, mesh)
    cfg_cache = {}
    rows = []
    for fn in sorted(os.listdir(d)):
        if not fn.endswith('.json') or '__' not in fn:
            continue
        shape_part = fn[:-5].split('__')[1]
        if shape_part not in SHAPE_TOKENS:          # skip tagged variants
            continue
        rows.append(analyze_cell(os.path.join(d, fn), cfg_cache))
    hdr = ('| arch | shape | compute s | memory s | collective s | dominant '
           '| useful (6ND/HLO) | HBM/dev GB | fits |')
    sep = '|' + '---|' * 9
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} "
            f"| {r['memory_s']:.3f} | {r['collective_s']:.3f} "
            f"| **{r['dominant']}** | {r['useful_ratio']:.2f} "
            f"| {r['hbm_bytes_per_device'] / 1e9:.1f} "
            f"| {'y' if r['fits_hbm'] else 'N'} |")
    table = '\n'.join(lines)
    print(table)
    with open(f'experiments/roofline_{mesh}.md', 'w') as f:
        f.write(table + '\n')
    with open(f'experiments/roofline_{mesh}.json', 'w') as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == '__main__':
    ap = argparse.ArgumentParser()
    ap.add_argument('--mesh', default='pod')
    args = ap.parse_args()
    main(args.mesh)
