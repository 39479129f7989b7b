"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, or unsupported collectives fail here.  Writes
per-cell JSON (memory analysis, FLOPs/bytes, per-kind collective bytes) that
benchmarks/roofline.py turns into the EXPERIMENTS.md tables.

Usage:
    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all --mesh pod
    python -m repro.launch.dryrun --all --mesh multipod
"""
import argparse
import json
import os
import re
import time

_DTYPE_BYTES = {'f64': 8, 'f32': 4, 'bf16': 2, 'f16': 2, 'f8e4m3': 1,
                'f8e5m2': 1, 's64': 8, 'u64': 8, 's32': 4, 'u32': 4,
                's16': 2, 'u16': 2, 's8': 1, 'u8': 1, 'pred': 1,
                'c64': 8, 'c128': 16}

_COLL_KINDS = ('all-gather', 'all-reduce', 'reduce-scatter', 'all-to-all',
               'collective-permute')

_SHAPE_RE = re.compile(r'(f64|f32|bf16|f16|f8e4m3|f8e5m2|s64|u64|s32|u32|'
                       r's16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]')


def _shape_bytes(m):
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(','):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collective_bytes(hlo: str):
    """Sum collective operand bytes from optimized HLO, scaling ops inside
    while loops (scan-over-layers) by their trip counts."""
    # split into computations
    comps = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r'(?:ENTRY )?%?([\w\.\-]+)[\w\s]*\(.*\)\s*->.*{\s*$',
                     line)
        if m and ('{' in line):
            if cur_name:
                comps[cur_name] = cur_lines
            cur_name, cur_lines = m.group(1), []
        elif cur_name is not None:
            cur_lines.append(line)
    if cur_name:
        comps[cur_name] = cur_lines

    def trip_count(cond_lines):
        consts = [int(x) for l in cond_lines
                  for x in re.findall(r'constant\((\d+)\)', l)]
        return max(consts) if consts else 1

    # collective bytes directly in each computation + while calls
    def comp_bytes(name, seen):
        if name in seen:
            return {}
        seen = seen | {name}
        totals: dict[str, float] = {}
        for line in comps.get(name, ()):
            for kind in _COLL_KINDS:
                if f' {kind}(' in line or f'{kind}-start(' in line:
                    args = line.split('(', 1)[1]
                    b = sum(_shape_bytes(m)
                            for m in _SHAPE_RE.finditer(args))
                    totals[kind] = totals.get(kind, 0) + b
                    break
            m = re.search(r'while\(', line)
            if m:
                bm = re.search(r'body=%?([\w\.\-]+)', line)
                cm = re.search(r'condition=%?([\w\.\-]+)', line)
                if bm:
                    inner = comp_bytes(bm.group(1), seen)
                    tc = trip_count(comps.get(cm.group(1), ())) if cm else 1
                    for k, v in inner.items():
                        totals[k] = totals.get(k, 0) + v * tc
        return totals

    entry = None
    for line in hlo.splitlines():
        if line.startswith('ENTRY'):
            m = re.match(r'ENTRY %?([\w\.\-]+)', line)
            entry = m.group(1) if m else None
            break
    if entry is None:
        # fall back: scan whole text flat (no loop scaling)
        totals = {}
        for line in hlo.splitlines():
            for kind in _COLL_KINDS:
                if f' {kind}(' in line or f'{kind}-start(' in line:
                    args = line.split('(', 1)[1]
                    b = sum(_shape_bytes(m) for m in _SHAPE_RE.finditer(args))
                    totals[kind] = totals.get(kind, 0) + b
                    break
        return totals
    return comp_bytes(entry, frozenset())


def run_cell(arch: str, shape: str, mesh_name: str, *, fsdp=True,
             int8=False, kv8=False, out_dir='experiments/dryrun',
             extra_tag=''):
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, SHAPES
    from repro.launch import steps as steps_lib
    from repro.optim.adamw import AdamWState  # noqa: F401

    t0 = time.time()
    cfg = get_config(arch)
    if kv8:
        cfg = cfg.replace(kv_cache_bits=8)
    mesh = make_production_mesh(multi_pod=(mesh_name == 'multipod'))
    info = SHAPES[shape]
    with mesh:
        if info['kind'] == 'train':
            batch = input_specs(cfg, shape)
            fn, model, (p_aval, o_aval, p_sh, o_sh) = \
                steps_lib.build_train_step(cfg, mesh, batch, fsdp=fsdp)
            lowered = fn.lower(p_aval, o_aval, batch)
        elif info['kind'] == 'prefill':
            batch = input_specs(cfg, shape)
            fn, model, (p_aval, p_sh) = steps_lib.build_prefill_step(
                cfg, mesh, batch, max_len=info['seq'], fsdp=fsdp)
            lowered = fn.lower(p_aval, batch)
        else:
            d = input_specs(cfg, shape)
            fn, model, (avals, in_sh) = steps_lib.build_serve_step(
                cfg, mesh, batch=d['batch'], max_len=d['max_len'],
                long_ctx=d['long_ctx'], fsdp=fsdp, int8_weights=int8)
            lowered = fn.lower(*avals)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch.hlo_analysis import analyze
    ana = analyze(hlo)
    coll = ana['collectives']
    res = {
        'arch': arch, 'shape': shape, 'mesh': mesh_name,
        'devices': int(len(mesh.devices.flat)),
        'flops_per_device': float(ana['flops']),
        'bytes_per_device': float(ana['bytes']),
        'xla_flops_unscaled': float(cost.get('flops', -1)),
        'xla_bytes_unscaled': float(cost.get('bytes accessed', -1)),
        'memory': {
            'argument_bytes': int(getattr(mem, 'argument_size_in_bytes', -1)),
            'output_bytes': int(getattr(mem, 'output_size_in_bytes', -1)),
            'temp_bytes': int(getattr(mem, 'temp_size_in_bytes', -1)),
            'alias_bytes': int(getattr(mem, 'alias_size_in_bytes', -1)),
        },
        'collective_bytes': coll,
        'lower_s': round(t_lower, 1), 'compile_s': round(t_compile, 1),
    }
    os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
    tag = f'{arch}__{shape}{extra_tag}.json'
    with open(os.path.join(out_dir, mesh_name, tag), 'w') as f:
        json.dump(res, f, indent=1)
    import gzip
    with gzip.open(os.path.join(out_dir, mesh_name,
                                tag[:-5] + '.hlo.gz'), 'wt') as f:
        f.write(hlo)
    print(json.dumps(res))
    return res


def main():
    # 512 virtual host devices for the multi-pod meshes; the count locks
    # when jax initializes its backend, so it is set here, before any jax
    # import, and never at module import
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch')
    ap.add_argument('--shape')
    ap.add_argument('--mesh', default='pod', choices=['pod', 'multipod'])
    ap.add_argument('--all', action='store_true')
    ap.add_argument('--no-fsdp', action='store_true')
    ap.add_argument('--int8', action='store_true')
    ap.add_argument('--kv8', action='store_true')
    ap.add_argument('--out', default='experiments/dryrun')
    ap.add_argument('--tag', default='')
    args = ap.parse_args()

    from repro.configs import ARCH_NAMES
    from repro.launch.specs import cells
    todo = cells(ARCH_NAMES) if args.all else [(args.arch, args.shape)]
    failures = []
    for arch, shape in todo:
        try:
            run_cell(arch, shape, args.mesh, fsdp=not args.no_fsdp,
                     int8=args.int8, kv8=args.kv8, out_dir=args.out,
                     extra_tag=args.tag)
        except Exception as e:                                # noqa: BLE001
            failures.append((arch, shape, repr(e)))
            print(f'FAIL {arch} {shape}: {e!r}')
    if failures:
        raise SystemExit(f'{len(failures)} cells failed: {failures}')


if __name__ == '__main__':
    main()
