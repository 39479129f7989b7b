"""CPU tests of the benchmark harness: the manifest, the trace reduction,
the op and byte arithmetic, the metric readers, the refusal to run
without a chip, and the check that decides ``correct`` (a sound run
passes; the int4 control and planted faults do not).

The harness runs here on a tiny configuration with the program's jnp
backend; nothing in this file is a device measurement.
"""
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, os.path.join(ROOT, 'src')):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402
import workcount  # noqa: E402

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_./-]{1,200}$')
SEED = 2 ** 33 + 5          # above 32 bits: seeds may be that large


def manifest():
    return harness.load_json(harness.MANIFEST)


def _line_ok(text):
    return 1 <= len(text) <= 200 and '\n' not in text and '\t' not in text


# ------------------------------------------------------------- manifest


def test_manifest_keys_names_and_units():
    m = manifest()
    assert set(m) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= len(m['paths']) <= 16
    for p in m['paths']:
        assert PATH.match(p) and not p.startswith('/') and '..' not in p
    assert len(m['command']) <= 32 and all(_line_ok(w) for w in m['command'])
    assert isinstance(m['run_seconds'], int) and 1 <= m['run_seconds'] <= 51
    names = []
    for c in m['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert _line_ok(c['source']) and _line_ok(c['why'])
        assert any(c['file'].startswith(p + '/') for p in m['paths'])
        assert len(c['reduced']) <= 16
        assert all(NAME.match(k) for k in c['reduced'])
        names.append(c['name'])
    cells = []
    for w in m['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert w['chips'] in (1, 4) and _line_ok(w['why'])
        assert w['config'] in names and NAME.match(w['traffic'])
        cells.append(w['name'])
    assert len({(w['config'], w['traffic']) for w in m['workloads']}) \
        == len(cells)
    assert {w['config'] for w in m['workloads']} == set(names)
    for e in m['end_to_end']:
        assert set(e) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert e['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= e['bound'] <= 0.25
        names.append(e['name'])
    assert 'setup_s' in {e['name'] for e in m['end_to_end']}
    for p in m['per_layer']:
        assert set(p) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert p['source'] in ('device_trace', 'program_span',
                               'program_counter', 'host_clock')
        assert _line_ok(p['layer'])
        names.append(p['name'])
    for x in m['end_to_end'] + m['per_layer']:
        assert UNIT.match(x['unit']) and x['better'] in ('lower', 'higher')
    names += cells
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert len(json.dumps(m)) <= 64 * 1024


def test_every_cell_reports_what_the_manifest_promises():
    m = manifest()
    e2e = {e['name']: e for e in m['end_to_end']}
    for w in m['workloads']:
        spec = harness.load_cell(w['name'])
        assert spec['traffic']['mode'] in ('backlog', 'closed')
        assert spec['limits']['logit_rel_err']['limit'] > 0
        ref = os.path.join(HERE, spec['config']['reference'])
        assert os.path.exists(ref)
        reported = {n for n, e in e2e.items()
                    if w['name'] in e.get('workloads', [w['name']])}
        assert 'setup_s' in reported and len(reported) >= 2
        layer = [p for p in m['per_layer'] if w['name'] in p['workloads']]
        assert layer
    for p in m['per_layer']:
        assert os.path.exists(os.path.join(HERE, 'metrics',
                                           p['name'] + '.py'))
        assert p['moves'] in e2e
        for w in p['workloads']:
            assert w in e2e[p['moves']].get('workloads', [w])


def test_config_files_hold_published_widths():
    r34 = harness.load_json(os.path.join(HERE, 'configs',
                                         'resnet34-cifar.json'))
    assert r34['stage_blocks'] == [3, 4, 6, 3]
    assert r34['stage_widths'] == [64, 128, 256, 512]
    vgg = harness.load_json(os.path.join(HERE, 'configs',
                                         'vgg19-cifar.json'))
    assert vgg['stage_blocks'] == [2, 2, 4, 4, 4]
    assert vgg['stage_widths'] == [64, 128, 256, 512, 512]
    for c in (r34, vgg):
        assert (c['image_size'], c['num_classes'], c['slots']) == (32, 10, 64)
        assert (c['w_bits'], c['a_bits']) == (8, 8)


# ------------------------------------------------------- trace reduction


def _ns(ms):
    return int(ms * 1e6)


def test_trace_reduction_busy_union_idle_and_window():
    host = [('bench.window', _ns(10), _ns(110)),
            ('bench.chunk', _ns(10), _ns(60)),
            ('bench.gather_rows', _ns(20), _ns(30)),
            ('bench.chunk', _ns(60), _ns(110)),
            ('bench.land', _ns(90), _ns(100))]
    ops = [('conv', _ns(0), _ns(15)),      # starts before the window
           ('conv', _ns(30), _ns(50)),
           ('gather', _ns(40), _ns(55)),   # overlaps: counted once
           ('conv', _ns(60), _ns(90)),
           ('conv', _ns(105), _ns(130))]   # ends after the window
    red = devtrace.reduce_trace({'/device:TPU:0': ops}, host)
    busy_ms = 5 + 25 + 30 + 5
    assert red['window_s'] == pytest.approx(0.1)
    assert red['busy_s'] == pytest.approx(busy_ms * 1e-3)
    assert red['idle_share'] == pytest.approx(1 - busy_ms / 100)
    assert red['n_ops'] == 5
    assert red['device_ops'][0] == ['conv', pytest.approx(0.060)]
    gaps = dict(red['idle_gaps'])
    # idle 15-30 (gather_rows mid 22.5), 55-60 (chunk), 90-105 (land)
    assert gaps['bench.gather_rows'] == pytest.approx(0.015)
    assert gaps['bench.chunk'] == pytest.approx(0.005)
    assert gaps['bench.land'] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.1 - busy_ms * 1e-3)


def test_trace_reduction_op_s_holds_every_op():
    host = [('bench.window', _ns(10), _ns(110))]
    # 14 op names on two chips: more than the top 10 of device_ops
    ops0 = [(f'op{i}', _ns(5 + 7 * i), _ns(12 + 7 * i)) for i in range(14)]
    ops1 = [(f'op{i}', _ns(20), _ns(21 + i)) for i in range(14)]
    red = devtrace.reduce_trace({'/device:TPU:0': ops0,
                                 '/device:TPU:1': ops1}, host)
    assert len(red['device_ops']) == 10
    assert set(red['op_s']) == {f'op{i}' for i in range(14)}
    for name, sec in red['device_ops']:
        assert red['op_s'][name] == sec
    clipped = sum(min(t1, _ns(110)) - max(t0, _ns(10))
                  for _, t0, t1 in ops0 + ops1
                  if t1 > _ns(10) and t0 < _ns(110)) * 1e-9
    assert sum(red['op_s'].values()) == pytest.approx(clipped)
    # op0 starts before the window: 5-12 ms on chip 0 counts 10-12
    assert red['op_s']['op0'] == pytest.approx(0.002 + 0.001)


def test_trace_reduction_averages_chips_and_needs_one_window():
    host = [('bench.window', 0, _ns(100))]
    red = devtrace.reduce_trace({'/device:TPU:0': [('a', 0, _ns(50))],
                                 '/device:TPU:1': [('a', 0, _ns(100))]},
                                host)
    assert red['busy_s'] == pytest.approx(0.075)
    with pytest.raises(ValueError):
        devtrace.reduce_trace({'/device:TPU:0': []}, host + host)
    with pytest.raises(ValueError):
        devtrace.reduce_trace({}, host)


def test_merge_and_labels():
    assert devtrace.merge([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4),
                                                                 (6, 7)]
    host = [('bench.a', 0, 10), ('bench.b', 2, 4), ('bench.c', 12, 14)]
    assert devtrace.labels_at(host, [1, 3, 5, 11, 13, 20]) == [
        'bench.a', 'bench.b', 'bench.a', devtrace.NO_SPAN, 'bench.c',
        devtrace.NO_SPAN]


# ------------------------------------------- configuration to program


def _nine_key_config(cfg, bits):
    """The construction the harness used before it passed every field."""
    from repro.configs.cnn import CNNConfig
    return CNNConfig(name=cfg['name'], kind=cfg['kind'],
                     num_classes=cfg['num_classes'],
                     in_channels=cfg['in_channels'],
                     stage_blocks=tuple(cfg['stage_blocks']),
                     stage_widths=tuple(cfg['stage_widths']),
                     w_bits=bits, a_bits=bits,
                     exit_stages=tuple(cfg['exit_stages']))


@pytest.mark.parametrize('name,bits', [('resnet34-cifar', 8),
                                       ('vgg19-cifar', 8),
                                       ('resnet34-cifar', 4)])
def test_program_config_of_the_shipped_configs(name, bits):
    cfg = harness.load_json(os.path.join(HERE, 'configs', name + '.json'))
    assert harness.program_config(cfg, bits) == _nine_key_config(cfg, bits)


@pytest.mark.parametrize('key,value', [('expand_ratio', 6),
                                       ('stage_widths', [8, 16, 24])])
def test_program_config_passes_every_field(key, value):
    cfg = _tiny('mobilenet')
    cfg[key] = value
    got = getattr(harness.program_config(cfg, 8), key)
    assert got == (tuple(value) if isinstance(value, list) else value)


def test_unknown_config_key_fails_before_any_weights(monkeypatch):
    cell, spec = _spec('resnet', 'exit-backlog')
    spec['config']['head_widht'] = 1280

    def no_reference(*a, **k):
        raise AssertionError('the reference was loaded')
    monkeypatch.setattr(harness, 'load_module', no_reference)
    with pytest.raises(ValueError, match="no field 'head_widht'"):
        harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                    spec=spec, require_chip=False)


@pytest.mark.parametrize('change', [{'expand_ratio': None},
                                    {'expand_ratio': 1}])
def test_program_config_refuses_a_mobilenet_the_count_reads_otherwise(
        change):
    cfg = _tiny('mobilenet')
    for key, value in change.items():
        if value is None:
            del cfg[key]
        else:
            cfg[key] = value
    with pytest.raises(ValueError, match='expand'):
        harness.program_config(cfg, 8)


# ------------------------------------------------------ op arithmetic


def _tiny(kind):
    cfg = harness.load_json(os.path.join(HERE, 'configs',
                                         'resnet34-cifar.json'))
    cfg.update(kind=kind, stage_blocks=[1, 2, 1], stage_widths=[8, 16, 32],
               image_size=16, exit_stages=[0, 1], slots=8,
               calibration_images=16)
    if kind == 'mobilenet':
        cfg['expand_ratio'] = 4
    return cfg


@pytest.mark.parametrize('kind', ['resnet', 'vgg', 'mobilenet'])
def test_layer_macs_match_the_programs_layer_plan(kind):
    import jax
    from repro.core.export import export_cnn
    from repro.models.cnn import init_cnn
    cfg = _tiny(kind)
    pcfg = harness.program_config(cfg, 8)
    if kind == 'mobilenet':     # the reference file has no mobilenet
        params = init_cnn(jax.random.key(1), pcfg)
    else:
        ref = harness.load_module(os.path.join(HERE, cfg['reference']),
                                  'ref')
        params = ref.init(jax.random.key(1), cfg)
    x = jax.random.normal(jax.random.key(2), (4, 16, 16, 3))
    model = export_cnn(params, pcfg, calibrate=x)
    plan = {n: e['macs'] for n, e in model.plan.layers.items()}
    mine = {lyr['name']: lyr['macs'] for lyr in workcount.layers(cfg)}
    assert mine == plan
    for lyr in workcount.layers(cfg):
        e = model.plan.layers[lyr['name']]
        assert lyr['in_elems'] == math.prod(e['in_shape'][1:])
        assert lyr['out_elems'] == math.prod(e['out_shape'][1:])


# per-segment MACs, conv and fc weights, and layers of each shipped
# configuration, as counted since the benchmark began
SHIPPED_COUNTS = {
    'resnet34-cifar': ([513_475_840, 645_927_936], 21_266_368, 38),
    'vgg19-cifar': ([266_013_184, 169_874_432], 20_063_424, 19),
}


@pytest.mark.parametrize('name', sorted(SHIPPED_COUNTS))
def test_layer_counts_of_the_shipped_configs(name):
    cfg = harness.load_json(os.path.join(HERE, 'configs', name + '.json'))
    seg_macs, weights, n_layers = SHIPPED_COUNTS[name]
    lyrs = workcount.layers(cfg)
    assert [sum(lyr['macs'] for lyr in lyrs if lyr['seg'] == s)
            for s in range(workcount.n_segments(cfg))] == seg_macs
    assert sum(lyr['w_elems'] for lyr in lyrs) == weights
    assert len(lyrs) == n_layers
    assert {lyr['kind'] for lyr in lyrs} == {'conv', 'fc'}


# the paper's inverted-residual block (Sandler et al. 2018, Table 2: the
# (t, c, n, s) table, a 32-wide stem, the 320 -> 1280 1x1 conv ahead of
# the pool) at the CIFAR strides of kuangliu/pytorch-cifar
# models/mobilenetv2.py.  Two departures from kuangliu's file: no expand
# conv at t = 1 (kuangliu's Block always has its 1x1 conv1) and no 1x1
# shortcut convs (kuangliu projects where a stride-1 block changes width)
MOBILENETV2_CIFAR = {
    'name': 'mobilenetv2-cifar', 'kind': 'mobilenet', 'in_channels': 3,
    'image_size': 32, 'num_classes': 10, 'exit_stages': [],
    'stem_width': 32, 'stage_expand': [1, 6, 6, 6, 6, 6, 6],
    'stage_widths': [16, 24, 32, 64, 96, 160, 320],
    'stage_blocks': [1, 2, 3, 4, 3, 3, 1],
    'stage_strides': [1, 1, 2, 2, 1, 2, 1], 'head_width': 1280,
}


def test_mobilenetv2_cifar_counts():
    cfg = dict(MOBILENETV2_CIFAR)
    lyrs = workcount.layers(cfg)
    assert sum(lyr['macs'] for lyr in lyrs) == 87_976_448
    assert sum(lyr['w_elems'] for lyr in lyrs) == 2_202_560
    dw = [lyr for lyr in lyrs if lyr['kind'] == 'depthwise']
    assert len(dw) == 17
    assert sum(lyr['macs'] for lyr in dw) == 5_879_808
    names = [lyr['name'] for lyr in lyrs]
    assert 's0b0.expand' not in names          # t = 1: no expand conv
    assert names[:4] == ['stem', 's0b0.dw', 's0b0.project', 's1b0.expand']
    assert names[-2:] == ['head_conv', 'head']
    by = {lyr['name']: lyr for lyr in lyrs}
    assert by['stem']['out_elems'] == 32 * 32 * 32
    assert by['s2b0.dw']['in_elems'] == 32 * 32 * 144     # stride 2 here
    assert by['s2b0.dw']['out_elems'] == 16 * 16 * 144
    assert by['s6b0.dw']['out_elems'] == 4 * 4 * 960
    assert by['head_conv']['macs'] == 4 * 4 * 320 * 1280
    assert by['head']['macs'] == 1280 * 10
    peaks = workcount.load_peaks('TPU v5 lite')
    assert workcount.segment_ops(cfg, 0, 1) == 2 * 87_976_448
    # every layer is bound by HBM bytes at a 64-image batch, so the
    # segment's least time is the sum of its layers' byte times
    byte_s = {}
    for lyr in lyrs:
        ops, nbytes = workcount.layer_ops_bytes(lyr, 64)
        assert ops / peaks['int8_ops_per_s'] \
            < nbytes / peaks['hbm_bytes_per_s'], lyr['name']
        byte_s[lyr['name']] = nbytes / peaks['hbm_bytes_per_s']
    assert workcount.segment_least_s(cfg, 0, 64, peaks) \
        == pytest.approx(sum(byte_s.values()))
    del cfg['stage_expand']
    with pytest.raises(KeyError, match='expand'):
        workcount.layers(cfg)
    cfg['expand_ratio'] = 6     # uniform: s0 gains its expand conv
    assert 's0b0.expand' in [lyr['name'] for lyr in workcount.layers(cfg)]


def test_resnet34_segment_split():
    cfg = harness.load_json(os.path.join(HERE, 'configs',
                                         'resnet34-cifar.json'))
    macs = [sum(lyr['macs'] for lyr in workcount.layers(cfg)
                if lyr['seg'] == s) for s in range(2)]
    # one exit head: segment 0 = stem + stages 0-1 and the exit head,
    # segment 1 = stages 2-3 + the final head
    assert workcount.n_segments(cfg) == 2
    assert [round(m / 1e6) for m in macs] == [513, 646]
    assert workcount.segment_ops(cfg, 0, 2) == 2 * 2 * macs[0]
    assert workcount.segments_of_answer(cfg, 1) == 1
    assert workcount.segments_of_answer(cfg, -1) == 2
    peaks = workcount.load_peaks('TPU v5 lite')
    least = workcount.segment_least_s(cfg, 0, 64, peaks)
    assert 2 * 64 * macs[0] / peaks['int8_ops_per_s'] <= least
    with pytest.raises(KeyError):
        workcount.load_peaks('TPU v9 imaginary')


def test_readers_leave_out_what_they_cannot_read():
    m = manifest()
    cfg = harness.load_json(os.path.join(HERE, 'configs',
                                         'resnet34-cifar.json'))
    peaks = workcount.load_peaks('TPU v5 lite')
    backlog = SimpleNamespace(
        cfg=cfg, traffic={'mode': 'backlog'}, peaks=peaks,
        trace={'busy_s': 8.0, 'window_s': 10.0, 'idle_share': 0.2},
        window_s=10.0, exit_mix={1: 16000, -1: 16000},
        segment_batches=[500, 250], slots=64,
        spans=[SimpleNamespace(name='export.calibrate', t0=1.0, t1=3.5)],
        compile_s=4.0)
    got = harness.per_layer_metrics(m, 'resnet34-exit-backlog', set(),
                                    backlog)
    assert got['idle_share.backlog']['value'] == pytest.approx(20.0)
    assert 0 < got['kernel_roofline.backlog']['value'] < 100
    assert 0 < got['mfu.backlog']['value'] < 100
    assert got['calibrate_s']['value'] == pytest.approx(2.5)
    assert got['compile_s']['value'] == 4.0
    assert all(v['unit'] == p['unit'] for p in m['per_layer']
               for k, v in got.items() if k == p['name'])
    untraced = SimpleNamespace(**dict(vars(backlog), trace=None, peaks=None,
                                      spans=[]))
    got = harness.per_layer_metrics(m, 'resnet34-exit-backlog', set(),
                                    untraced)
    assert set(got) == {'compile_s'}


def test_host_spans_wrap_restore_and_refuse_a_missing_target(monkeypatch):
    from repro.serving import scheduler
    orig = scheduler._gather_rows
    with harness.host_spans():
        assert scheduler._gather_rows is not orig
    assert scheduler._gather_rows is orig
    monkeypatch.delattr(scheduler, '_gather_rows')
    with pytest.raises(RuntimeError, match='_gather_rows'):
        with harness.host_spans():
            pass


# ------------------------------------------------- no chip, no program


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS='cpu', **(env_extra or {}))
    return subprocess.run(
        [sys.executable, 'bench/run.py', '--workload',
         'resnet34-exit-backlog', '--seed', str(SEED), '--seconds', '1',
         '--trace', '0'], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_exits_nonzero_without_a_tpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert 'TPU' in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith('{')]


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(harness.MANIFEST, tmp_path / 'BENCHMARK.json')
    shutil.copytree(HERE, tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    out = _run_cli(tmp_path, {'PYTHONPATH': ''})
    assert out.returncode != 0
    assert out.stdout.strip() == ''


# ------------------------------------------- correct: sound, control, faults


def _spec(kind, traffic):
    m = manifest()
    cell = {'exit-backlog': 'resnet34-exit-backlog',
            'full-backlog': 'resnet34-full-backlog',
            'single-closed': 'resnet34-single'}[traffic]
    tr = harness.load_json(os.path.join(HERE, 'traffic', traffic + '.json'))
    tr.update(pool=64, check_sample=32)
    if tr['mode'] == 'backlog':
        tr['chunk'] = 64
    else:
        tr['warmup_requests'] = 4
    return cell, {'cell': [w for w in m['workloads'] if w['name'] == cell][0],
                  'manifest': m, 'config': _tiny(kind), 'traffic': tr,
                  'limits': harness.load_cell(cell)['limits']}


def _run(kind='resnet', traffic='exit-backlog', bits=None, diag=None):
    cell, spec = _spec(kind, traffic)
    return harness.run(cell, SEED, 0.2, False, t_start=time.perf_counter(),
                       spec=spec, require_chip=False, bits=bits, diag=diag)


@pytest.mark.parametrize('kind,traffic', [('resnet', 'exit-backlog'),
                                          ('vgg', 'exit-backlog'),
                                          ('resnet', 'full-backlog'),
                                          ('resnet', 'single-closed')])
def test_sound_run_is_correct(kind, traffic):
    diag = {}
    r = _run(kind, traffic, diag=diag)
    assert r['correct'], r['checks']
    assert r['failed'] == 0 and r['attempted'] > 0
    assert list(r)[-1] == 'checks'
    assert set(r['metrics']) >= {'setup_s'}
    if traffic == 'full-backlog':
        assert set(diag['exit_mix']) == {-1}


def test_int4_control_is_not_correct():
    r = _run(bits=4)
    assert not r['correct']
    c = r['checks']['logit_rel_err']
    assert c['value'] > 3 * _run()['checks']['logit_rel_err']['value']


def test_fault_answer_altered_where_produced(monkeypatch):
    from repro.serving.scheduler import ContinuousBatchScheduler
    orig = ContinuousBatchScheduler._complete

    def altered(self, req, logits_row, *a, **k):
        if req.rid % 2 == 0:
            logits_row = np.array(logits_row, copy=True)
            logits_row[0] += 0.5 * np.abs(logits_row).max()
        return orig(self, req, logits_row, *a, **k)
    monkeypatch.setattr(ContinuousBatchScheduler, '_complete', altered)
    r = _run()
    assert not r['correct']
    assert r['checks']['oracle_mismatch']['value'] > 0
    assert r['checks']['logit_rel_err']['value'] \
        > r['checks']['logit_rel_err']['limit']


def test_fault_answers_given_to_other_requests(monkeypatch):
    from repro.serving.scheduler import ContinuousBatchScheduler
    orig = ContinuousBatchScheduler._land

    def rolled(self, k, items, out, *a, **kw):
        return orig(self, k, items[1:] + items[:1], out, *a, **kw)
    monkeypatch.setattr(ContinuousBatchScheduler, '_land', rolled)
    r = _run()
    assert not r['correct']
    assert r['checks']['oracle_mismatch']['value'] > 0
