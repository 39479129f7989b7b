"""One run of one benchmark cell: set-up, the measured window, the check.

``run()`` is the whole run; ``bench/run.py`` is its command line.  Tests
call ``run()`` directly with a tiny configuration on the CPU
(``require_chip=False``).

Configuration file: the keys in ``HARNESS_KEYS`` are the harness's own
(sizes of the run, the reference file, the record of cuts and
departures); every other key is an architecture key and goes to the
program's ``CNNConfig`` field of that name (``program_config``), and the
benchmark's work count reads the same keys (bench/workcount.py).  A key
that is neither is refused with ``ValueError`` before any weights are
made.

Set-up (``setup_s``, from process start to the first timed request):
random weights from the seed in one jitted call (the reference file's
``init``), the image pool in another, the int8-resident export
``export_cnn(params, cfg, calibrate=<images>)`` with a ``Tracer`` (its
``export.calibrate`` span is ``calibrate_s``), then (``compile_s``) every
segment program built or loaded at the slot geometry, the exit threshold
over the whole pool, and a warm-up of the cell's own traffic.

Window: the traffic file's load loop (bench/loadgen.py) around
``ContinuousBatchScheduler.run_trace``, on the host's wall clock.
Compilations inside it are counted; there should be none.  With
``trace=1`` the window runs under the JAX profiler and the per-layer
metrics are read from it.

Check (``correct``), once the window has closed: a seed-drawn sample of
the answers, the deepest exits first, is compared

* with the plain float32 reference (the configuration's reference file,
  on the same weights and images): ``logit_rel_err``, the largest
  ``||served - reference|| / ||reference||`` over the sample, each answer
  against the reference head it claims to come from;
* with the program's own stage programs run on the sampled images alone
  and the exit rule applied to their heads (``oracle_mismatch``, exact):
  each answer belongs to its request and its exit decision;
* and every request handed in must have been answered (``unanswered``).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(ROOT, 'BENCHMARK.json')
BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
# configuration-file keys the harness reads or keeps for the reader; every
# other key is an architecture key and must name a ``CNNConfig`` field
HARNESS_KEYS = frozenset({
    'source', 'image_size', 'slots', 'calibration_images', 'reference',
    'reduced', 'departures', 'deployment', 'assumed'})


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, manifest_path: str = MANIFEST) -> dict:
    """The cell's manifest entries and the files they name."""
    manifest = load_json(manifest_path)
    cells = {w['name']: w for w in manifest['workloads']}
    if workload not in cells:
        raise KeyError(f'no workload {workload!r} in {manifest_path}')
    cell = cells[workload]
    conf = {c['name']: c for c in manifest['configs']}[cell['config']]
    return {
        'cell': cell, 'manifest': manifest,
        'config': load_json(os.path.join(ROOT, conf['file'])),
        'traffic': load_json(os.path.join(BENCH, 'traffic',
                                          cell['traffic'] + '.json')),
        'limits': load_json(os.path.join(BENCH, 'limits',
                                         workload + '.json')),
    }


def key_from_seed(seed: int):
    """A PRNG key that keeps all 64 bits of ``seed`` (``jax.random.key``
    drops the high word of a seed above 2**32)."""
    import jax
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f'seed {seed} is outside [0, 2**64)')
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def program_config(cfg, bits):
    """The program's ``CNNConfig`` for a configuration file's dict: every
    key that names a ``CNNConfig`` field is passed on (lists as tuples),
    with ``w_bits``/``a_bits`` set to ``bits``.  A key that is neither a
    field nor one of ``HARNESS_KEYS`` is a model the program cannot build:
    ``ValueError``, never a silently different model.  So is a mobilenet
    that the program and the work count would read differently: one with
    no expansion key (the program would take its default), or with
    ``expand_ratio`` 1 (the program still builds the expand conv that the
    count leaves out at t = 1)."""
    import dataclasses
    from repro.configs.cnn import CNNConfig
    fields = {f.name for f in dataclasses.fields(CNNConfig)}
    unknown = sorted(set(cfg) - fields - HARNESS_KEYS)
    if unknown:
        raise ValueError(f'configuration {cfg.get("name")!r}: the program '
                         f'has no field {", ".join(map(repr, unknown))}')
    if cfg.get('kind') == 'mobilenet':
        if 'stage_expand' not in cfg and 'expand_ratio' not in cfg:
            raise ValueError(f'mobilenet configuration {cfg.get("name")!r} '
                             f'states neither stage_expand nor expand_ratio')
        if cfg.get('expand_ratio') == 1:
            raise ValueError(f'configuration {cfg.get("name")!r}: '
                             f'expand_ratio 1, but the program builds an '
                             f'expand conv at t = 1 and the work count '
                             f'does not')
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in cfg.items() if k in fields}
    return CNNConfig(**dict(kw, w_bits=bits, a_bits=bits))


def exit_threshold(model, pool, slots, rule):
    """The traffic's exit threshold: fixed, or the confidence at which a
    ``quantile`` share of the whole pool leaves at the first head (the rule
    of the program's ``calibrate_exit_threshold``, over the pool in
    ``slots``-image batches of segment 0)."""
    if 'fixed' in rule:
        return float(rule['fixed'])
    import jax.numpy as jnp
    from repro.core.export import exit_confidence
    conf = []
    for i in range(0, len(pool), slots):
        exits, _ = model.run_stage(0, pool[i:i + slots])
        conf.append(np.asarray(exit_confidence(exits[min(exits)])))
    conf = jnp.asarray(np.concatenate(conf)[:len(pool)])
    return float(jnp.quantile(conf, 1.0 - rule['quantile'])) - 1e-6


class CompileCounter:
    """Counts executables built or loaded while ``on`` (JAX reports one
    backend-compile event for each, from the persistent cache or not)."""

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.n += 1


@contextlib.contextmanager
def host_spans():
    """Profiler annotations around the scheduler's host steps and the
    segment dispatch, for the traced run's idle-gap labels.  Wraps them
    and restores them afterwards; changes no result.  Until the program
    has spans of its own there, a renamed target is an error, not a
    silently unlabelled gap."""
    import jax
    from repro.core import export
    from repro.serving import scheduler
    targets = [(scheduler, '_gather_rows', 'bench.gather_rows'),
               (scheduler.ContinuousBatchScheduler, '_land', 'bench.land'),
               (export.ServingModel, 'run_stage', 'bench.run_stage')]
    missing = [f'{getattr(o, "__name__", o)}.{a}' for o, a, _ in targets
               if not callable(getattr(o, a, None))]
    if missing:
        raise RuntimeError(f'traced run: no {", ".join(missing)} to span')
    saved = []
    for obj, attr, name in targets:
        fn = getattr(obj, attr)

        def wrapped(*a, _fn=fn, _name=name, **k):
            with jax.profiler.TraceAnnotation(_name):
                return _fn(*a, **k)
        saved.append((obj, attr, fn))
        setattr(obj, attr, wrapped)
    try:
        yield
    finally:
        for obj, attr, fn in saved:
            setattr(obj, attr, fn)


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def sample_answers(answers, k, rng):
    """Up to ``k`` answers ``(pool row, completion)`` to distinct pool rows,
    seed-drawn, with an equal share for each exit stage that occurred so
    the deepest are in."""
    seen, by_stage = set(), {}
    for i in rng.permutation(len(answers)):
        j, c = answers[i]
        if j not in seen:
            seen.add(j)
            by_stage.setdefault(c.exit_stage, []).append(i)
    picked = []
    share = max(k // max(len(by_stage), 1), 1)
    for stage in sorted(by_stage, key=lambda s: (s != -1, -s)):
        idx = by_stage[stage]
        picked += list(rng.choice(idx, min(share, len(idx)), replace=False))
    rest = np.setdiff1d([i for idx in by_stage.values() for i in idx],
                        picked)
    if len(picked) < k and len(rest):
        picked += list(rng.choice(rest, min(k - len(picked), len(rest)),
                                  replace=False))
    return [answers[i] for i in sorted(picked)]


def _batches(sample, pool, slots):
    """The sampled answers with their images, in ``slots``-row batches
    zero-padded to ``slots``: ``[(completions, images)]``."""
    out = []
    for i in range(0, len(sample), slots):
        part = sample[i:i + slots]
        x = pool[np.array([j for j, _ in part])]
        if len(part) < slots:
            x = np.concatenate([x, np.zeros((slots - len(part),)
                                            + x.shape[1:], x.dtype)])
        out.append(([c for _, c in part], x))
    return out


def oracle_mismatches(sample, model, pool, slots, threshold) -> int:
    """Answers that differ, in exit stage or in any bit of the logits,
    from the program's stage programs run on the sampled images in fresh
    batches with the exit rule applied to their heads."""
    import jax
    from repro.serving import exit_decisions
    bad = 0
    for comps, x in _batches(sample, pool, slots):
        logits, exits = jax.block_until_ready(model.serve_stages(x))
        stage, ans = exit_decisions(logits, exits, threshold)
        for r, c in enumerate(comps):
            if c.exit_stage != int(stage[r]) or not np.array_equal(
                    np.asarray(c.logits, np.float32), ans[r]):
                bad += 1
    return bad


def reference_errors(sample, forward, params, pool, slots) -> np.ndarray:
    """Per answer, ``||served - reference|| / ||reference||`` against the
    float32 reference's head that the answer claims to come from."""
    import jax
    fwd = jax.jit(forward)
    errs = []
    for comps, x in _batches(sample, pool, slots):
        final, exits = fwd(params, x)
        final = np.asarray(final, np.float64)
        exits = {s: np.asarray(v, np.float64) for s, v in exits.items()}
        for r, c in enumerate(comps):
            want = final[r] if c.exit_stage == -1 else exits[c.exit_stage][r]
            got = np.asarray(c.logits, np.float64)
            errs.append(np.linalg.norm(got - want)
                        / max(np.linalg.norm(want), 1e-12))
    return np.array(errs)


def per_layer_metrics(manifest, workload, reported, ctx):
    """Per-layer metrics that belong to this cell, each read by its own
    reader ``bench/metrics/<name>.py``; a reader that finds nothing
    returns None and its metric is left out.  ``ctx.trace`` is
    ``devtrace.reduce_trace``'s dict in a traced run (``op_s`` holds every
    device op's seconds), None otherwise."""
    out = {}
    for m in manifest['per_layer']:
        if workload not in m['workloads']:
            continue
        reader = load_module(os.path.join(BENCH, 'metrics',
                                          m['name'] + '.py'),
                             'bench_metric_' + m['name'].replace('.', '_'))
        v = reader.read(ctx)
        if v is not None:
            out[m['name']] = {'value': float(v), 'unit': m['unit']}
    return out


def run(workload, seed, seconds, trace, *, t_start, spec=None,
        require_chip=True, bits=None, diag=None):
    """One run of ``workload``; returns the result line's dict.

    ``spec`` (tests) gives ``{'cell', 'manifest', 'config', 'traffic',
    'limits'}`` in place of the files; ``bits`` (the control) serves at
    another bit width than the configuration states; ``diag``, a dict,
    receives every sampled answer's error, the exit mix and the
    threshold."""
    spec = spec or load_cell(workload)
    cell, manifest = spec['cell'], spec['manifest']
    cfg, traffic, limits = spec['config'], spec['traffic'], spec['limits']

    import jax
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != 'tpu':
            raise NoChip(f'JAX platform is {devices[0].platform!r}; this '
                         f'benchmark measures a TPU and has no fallback')
        if len(devices) < cell['chips']:
            raise NoChip(f'{len(devices)} chip(s), the cell needs '
                         f'{cell["chips"]}')
    dev = devices[0]

    from repro.core.export import export_cnn
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.trace import Tracer
    from repro.serving import ContinuousBatchScheduler, Request
    import loadgen as gen
    import workcount

    if require_chip:
        use_compile_cache()
        # the export's calibration forward is eager: keep its small
        # programs in the persistent cache too, so that only a checkout's
        # first run builds them
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    # a configuration the program cannot express fails here, before any
    # weights are made
    pcfg = program_config(cfg, bits or cfg['w_bits'])
    counter = CompileCounter()
    ref = load_module(os.path.join(BENCH, cfg['reference']),
                      'bench_ref_' + cfg['name'].replace('-', '_'))
    rng = np.random.default_rng(int(seed))
    slots = cfg['slots']

    key = key_from_seed(seed)
    params = jax.jit(lambda k: ref.init(k, cfg))(jax.random.fold_in(key, 0))
    n_pool, n_cal = traffic['pool'], cfg['calibration_images']
    img = traffic['images']
    images = jax.jit(lambda k: gen.make_images(
        k, n_pool + n_cal, size=cfg['image_size'],
        channels=cfg['in_channels'], classes=img['classes'],
        jitter=img['jitter'], difficulty=img['difficulty'],
        scale_sd=img['scale_sd']))(jax.random.fold_in(key, 1))
    pool = np.asarray(images[:n_pool])
    calib = images[n_pool:]
    order = rng.permutation(n_pool)

    tracer = Tracer()
    model = export_cnn(params, pcfg, calibrate=calib, tracer=tracer)
    del images, calib
    if model.n_stages != workcount.n_segments(cfg):
        raise RuntimeError(f'export has {model.n_stages} segments, the '
                           f'configuration {workcount.n_segments(cfg)}')
    if require_chip and model.backend != 'pallas':
        raise RuntimeError(f'export backend is {model.backend!r}, not the '
                           f'Pallas kernels')
    t0 = time.perf_counter()
    jax.block_until_ready(model.serve_stages(pool[:slots]))  # every segment
    threshold = exit_threshold(model, pool, slots, traffic['threshold'])
    sched = ContinuousBatchScheduler(model, slots=slots, threshold=threshold)
    if traffic['mode'] == 'backlog':
        gen.run_backlog(sched, pool, order, traffic['chunk'], 0.0, Request)
    else:       # every segment at one live row, then the cell's own load
        gen.run_closed(ContinuousBatchScheduler(
            model, slots=slots, threshold=2.0), pool, order, 0.0, Request,
            n=1)
        gen.run_closed(sched, pool, order, 0.0, Request,
                       n=traffic['warmup_requests'])
    compile_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    calibrate_s = sum(s.dur for s in tracer.spans
                      if s.name == 'export.calibrate')
    print(f'[setup] workload={workload} seed={seed} setup_s={setup_s:.6f} '
          f'calibrate_s={calibrate_s:.6f} compile_s={compile_s:.6f} '
          f'threshold={threshold:.6f}', flush=True)

    trace_dir = tempfile.TemporaryDirectory() if trace else None
    spans = host_spans() if trace else contextlib.nullcontext()
    span = _span if trace else gen._no_span
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    counter.on = True
    with spans, span('bench.window'):
        if traffic['mode'] == 'backlog':
            win = gen.run_backlog(sched, pool, order, traffic['chunk'],
                                  seconds, Request, span=span)
        else:
            win = gen.run_closed(sched, pool, order, seconds, Request,
                                 span=span)
    counter.on = False
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get('peak_bytes_in_use', 0))
    red = None
    if trace:
        import devtrace
        jax.profiler.stop_trace()
        red = devtrace.reduce_trace(*devtrace.read_xplane(
            devtrace.find_xplane(trace_dir.name)))
        trace_dir.cleanup()

    # what the window answered, and the program's own counts
    if traffic['mode'] == 'backlog':
        answers = [(order[rid], c) for comp, _ in win['chunks']
                   for rid, c in sorted(comp.items())]
        seg_batches = [0] * model.n_stages
        occupancy = None
        for _, m in win['chunks']:
            s = m.summary()
            for k, v in s['n_batches'].items():
                seg_batches[int(k)] += v
            occupancy = s['batch_occupancy']
    else:
        answers = [(j, c) for j, c in win['answers'] if c is not None]
        seg_batches = occupancy = None
    exit_mix = {}
    for _, c in answers:
        exit_mix[c.exit_stage] = exit_mix.get(c.exit_stage, 0) + 1
    failed = win['attempted'] - len(answers)
    print(f'[window] seconds={win["window_s"]:.6f} '
          f'attempted={win["attempted"]} answered={len(answers)} '
          f'compiles_in_window={counter.n} '
          f'exit_mix={json.dumps(exit_mix, sort_keys=True)} '
          f'segment_batches={seg_batches} '
          f'batch_occupancy_last_chunk={json.dumps(occupancy)}', flush=True)

    sample = sample_answers(answers, traffic['check_sample'], rng)
    mismatch = oracle_mismatches(sample, model, pool, slots, threshold)
    del sched, model                 # the program's state, before the reference
    errs = reference_errors(sample, lambda p, x: ref.forward(p, cfg, x),
                            params, pool, slots)
    if diag is not None:
        diag.update(rel_errs=errs, exit_mix=exit_mix, threshold=threshold)
    checks = {
        'logit_rel_err': {'value': float(errs.max()),
                          'limit': limits['logit_rel_err']['limit']},
        'oracle_mismatch': {'value': mismatch, 'limit': 0},
        'unanswered': {'value': failed, 'limit': 0},
    }
    correct = all(c['value'] <= c['limit'] for c in checks.values())
    print(f'[check] sampled={len(sample)} median_rel_err='
          f'{float(np.median(errs)):.6g}', flush=True)

    reported = {m['name'] for m in manifest['end_to_end']
                if workload in m.get('workloads', [workload])}
    e2e = {'setup_s': setup_s}
    if traffic['mode'] == 'backlog':
        e2e['images_per_s'] = len(answers) / win['window_s']
    else:
        e2e['latency_p95_ms'] = float(
            np.percentile(win['latency_s'], 95)) * 1e3
    units = {m['name']: m['unit'] for m in manifest['end_to_end']}
    if trace:
        from types import SimpleNamespace
        peaks = (workcount.load_peaks(dev.device_kind) if require_chip
                 else None)
        ctx = SimpleNamespace(
            cfg=cfg, traffic=traffic, peaks=peaks, trace=red,
            window_s=win['window_s'], exit_mix=exit_mix,
            segment_batches=seg_batches, slots=slots,
            spans=tracer.spans, compile_s=compile_s)
        metrics = per_layer_metrics(manifest, workload, reported, ctx)
    else:
        metrics = {k: {'value': v, 'unit': units[k]} for k, v in e2e.items()
                   if k in reported}
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(devices), 'memory_peak_bytes': memory_peak}
    result = {'correct': bool(correct), 'attempted': win['attempted'],
              'failed': failed, 'metrics': metrics, 'device': device}
    if red is not None:
        device['busy_s'] = red['busy_s']
        device['window_s'] = red['window_s']
        result['breakdown'] = {'device_ops': red['device_ops'],
                               'idle_gaps': red['idle_gaps']}
    result['checks'] = checks
    return result
