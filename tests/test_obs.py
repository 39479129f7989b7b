"""Observability-layer tests: the tracer's Chrome-trace export must
round-trip losslessly (a written trace is a checkable artifact, not a
picture), check_trace must be green on real scheduler/pool runs and red
on each seeded corruption, the analysis registry's trace-invariants rule
must fire on its mutant, and the export's measure-mode kernel timing must
surface both kernel.launch spans and the measured-vs-modeled
lowering_cost_delta block."""
import json

import jax
import numpy as np
import pytest

from repro.configs.cnn import RESNET8_CIFAR
from repro.core.export import calibrate_exit_threshold, export_cnn
from repro.core.family import CNNFamily
from repro.data import SyntheticImages
from repro.obs import (NULL_TRACER, NullTracer, Span, TraceInvariantError,
                       Tracer, as_tracer, check_trace, load_chrome_trace,
                       spans_to_chrome)
from repro.serving import (ChaosPlan, ContinuousBatchScheduler,
                           ReplicaPoolScheduler, Request)

SLOTS = 8
COSTS = [4e-3, 2e-3, 1e-3]


@pytest.fixture(scope='module')
def exported():
    fam = CNNFamily(SyntheticImages())
    base = RESNET8_CIFAR
    params = fam.init(jax.random.key(0), base)
    params, cfg = fam.add_exits(jax.random.key(2), params, base,
                                fam.default_exit_points(base))
    cfg = cfg.replace(w_bits=8, a_bits=8)
    calib = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    model = export_cnn(params, cfg, calibrate=calib)
    return model, calibrate_exit_threshold(model, calib)


def _trace(n, rate=2000.0, seed=0):
    xs = jax.random.normal(jax.random.key(11), (max(n, 1), 32, 32, 3))
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(i, xs[i], float(t[i])) for i in range(n)]


# ------------------------------------------------------------ tracer core


def test_null_tracer_is_allocation_free_default():
    assert as_tracer(None) is NULL_TRACER
    assert not NULL_TRACER.enabled
    NULL_TRACER.add('x', 0, 1, track='t')
    NULL_TRACER.async_span('x', 0, 1, track='t', cid=0)
    NULL_TRACER.instant('x', 0, track='t')
    with NULL_TRACER.span('x', track='t'):
        pass
    assert NULL_TRACER.spans == []
    t = Tracer()
    assert as_tracer(t) is t and t.enabled
    assert isinstance(NULL_TRACER, NullTracer)


def test_tracer_span_contextmanager_uses_wall_clock():
    t = Tracer()
    with t.span('export.calibrate', track='export', config='c'):
        pass
    (s,) = t.spans
    assert s.name == 'export.calibrate' and s.args == {'config': 'c'}
    assert 0.0 <= s.t0 <= s.t1
    assert s.dur == s.t1 - s.t0


def test_chrome_roundtrip_all_kinds(tmp_path):
    t = Tracer()
    t.add('stage.exec', 0.001, 0.005, track='replica0',
          stage=0, live=8, slots=8, rids=[0, 1])
    t.add('failover.restore', 0.005, 0.009, track='replica10',
          replaced=0)
    t.async_span('request.queue', 0.000, 0.001, track='cohort0', cid=1,
                 requeued=False)
    t.instant('compaction', 0.005, track='replica0', stage=0, n_exit=4,
              n_survive=4)
    path = str(tmp_path / 'trace.json')
    t.write(path)
    got = load_chrome_trace(path)
    assert sorted(s.name for s in got) == sorted(s.name for s in t.spans)
    by_name = {s.name: s for s in got}
    for orig in t.spans:
        g = by_name[orig.name]
        assert g.kind == orig.kind and g.track == orig.track
        assert g.t0 == pytest.approx(orig.t0, abs=1e-9)
        assert g.t1 == pytest.approx(orig.t1, abs=1e-9)
    assert by_name['request.queue'].cid == 1
    assert by_name['stage.exec'].args['rids'] == [0, 1]
    # process/thread structure: serving tracks in pid 1 in natural order
    # (replica10 after replica0), cohort in pid 2
    doc = json.load(open(path))
    names = {(e['pid'], e['tid']): e['args']['name']
             for e in doc['traceEvents']
             if e.get('ph') == 'M' and e['name'] == 'thread_name'}
    assert names[(1, 1)] == 'replica0' and names[(1, 2)] == 'replica10'
    assert any(pid == 2 for pid, _ in names)
    procs = {e['pid']: e['args']['name'] for e in doc['traceEvents']
             if e.get('ph') == 'M' and e['name'] == 'process_name'}
    assert procs[1] == 'serving' and procs[2] == 'requests'


def test_load_chrome_trace_rejects_torn_async():
    doc = spans_to_chrome([Span('request.queue', 0.0, 1.0, 'cohort0',
                                kind='async', cid=5)])
    doc['traceEvents'] = [e for e in doc['traceEvents']
                          if e.get('ph') != 'e']
    with pytest.raises(ValueError, match='torn async'):
        load_chrome_trace(doc)


# ----------------------------------------------------------- check_trace


def test_check_trace_clean_and_each_corruption():
    clean = [
        Span('stage.exec', 0.000, 0.004, 'replica0',
             args={'stage': 0, 'live': 8, 'slots': 8, 'rids': [0]}),
        Span('stage.exec', 0.004, 0.006, 'replica0',
             args={'stage': 1, 'live': 4, 'slots': 8, 'rids': [0]}),
    ]
    assert check_trace(clean) == []
    torn = [Span('stage.exec', 0.010, 0.008, 'replica1',
                 args={'stage': 0})]
    assert any('torn' in m for m in check_trace(torn))
    overlap = clean + [Span('stage.exec', 0.002, 0.005, 'replica0',
                            args={'stage': 0, 'rids': [9]})]
    assert any('concurrent' in m or 'overlaps' in m
               for m in check_trace(overlap))
    missing = [Span('stage.exec', 0.0, 0.001, 'replica0')]
    assert any('missing "stage"' in m for m in check_trace(missing))
    with pytest.raises(TraceInvariantError) as ei:
        check_trace(torn, strict=True)
    assert ei.value.violations


def test_check_trace_completion_extents(exported):
    """With completions, the span tree must cover each latency exactly —
    and a shifted exec span is caught."""
    model, thr = exported
    reqs = _trace(2 * SLOTS)
    tracer = Tracer()
    comp, _ = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=thr, stage_costs=COSTS,
        tracer=tracer).run_trace(reqs)
    assert check_trace(tracer, comp) == []
    # corrupt: stretch the last stage.exec past the completion time
    spans = list(tracer.spans)
    i = max(range(len(spans)), key=lambda j: spans[j].t1
            if spans[j].name == 'stage.exec' else -1.0)
    s = spans[i]
    spans[i] = Span(s.name, s.t0, s.t1 + 1.0, s.track, s.kind, s.cid,
                    s.args)
    assert any('extent mismatch' in m for m in check_trace(spans, comp))


# ------------------------------------------------- scheduler integration


def test_continuous_scheduler_trace_is_valid(exported):
    model, thr = exported
    reqs = _trace(3 * SLOTS + 5)
    tracer = Tracer()
    comp, _ = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=thr, stage_costs=COSTS,
        tracer=tracer).run_trace(reqs)
    assert len(comp) == len(reqs)
    assert check_trace(tracer, comp, strict=True) == []
    queue = [s for s in tracer.spans if s.name == 'request.queue']
    assert sorted(s.cid for s in queue) == sorted(r.rid for r in reqs)
    execs = [s for s in tracer.spans if s.name == 'stage.exec']
    assert execs and all(s.track == 'executor0' for s in execs)
    assert any(s.name == 'compaction' for s in tracer.spans)


def test_pool_chaos_trace_shows_kill_and_failover(exported):
    """The chaos story must be legible in the trace: a killed stage.exec
    truncated at the kill on the victim's track, the requeued request's
    second queue span starting AT the kill (no double-counted wait), and
    failover.restore on the replacement's track — all while the full
    invariant check stays green."""
    model, thr = exported
    reqs = _trace(3 * SLOTS, rate=4000.0)
    tracer = Tracer()
    plan = ChaosPlan(kills=((4e-3, 0),))
    comp, met = ReplicaPoolScheduler(
        model, slots=SLOTS, threshold=thr, stage_costs=COSTS, replicas=2,
        min_replicas=2, chaos=plan, restore=lambda: model,
        restore_delay=COSTS[0], tracer=tracer).run_trace(reqs)
    assert len(comp) == len(reqs)
    assert check_trace(tracer, comp, strict=True) == []
    killed = [s for s in tracer.spans
              if s.name == 'stage.exec' and s.args.get('killed')]
    assert killed, 'kill left no killed stage.exec span'
    (kt,) = {s.track for s in killed}
    restores = [s for s in tracer.spans if s.name == 'failover.restore']
    assert restores and restores[0].track != kt, \
        'restore must land on the replacement replica, not the victim'
    assert restores[0].dur == pytest.approx(COSTS[0])
    t_kill = killed[0].t1
    requeued = [s for s in tracer.spans if s.name == 'request.queue'
                and s.args.get('requeued')]
    assert requeued, 'killed flight must requeue its requests'
    assert all(s.t0 == pytest.approx(t_kill) for s in requeued)
    # rids on the killed flight get exactly two queue spans
    rid = int(killed[0].args['rids'][0])
    qs = [s for s in tracer.spans
          if s.name == 'request.queue' and s.cid == rid]
    assert len(qs) == 2


# ------------------------------------------------ host spans, two sinks

_HOST = ('serve.trace', 'serve.round', 'serve.assemble',
         'serve.assemble.parts', 'serve.assemble.concat',
         'serve.assemble.pad', 'serve.dispatch', 'serve.sync', 'serve.land',
         'serve.land.fetch')
_PARENT = {'serve.round': 'serve.trace', 'serve.assemble': 'serve.round',
           'serve.assemble.parts': 'serve.assemble',
           'serve.assemble.concat': 'serve.assemble',
           'serve.assemble.pad': 'serve.assemble',
           'serve.dispatch': 'serve.round', 'serve.sync': 'serve.round',
           'serve.land': 'serve.round', 'serve.land.fetch': 'serve.land'}


def test_scheduler_host_spans_nest_on_the_host_track(exported):
    """One serve.round per executed batch, each child inside its parent,
    every serve.* span on the wall-clock host track, and the whole trace
    (virtual-clock spans included) still valid after a Chrome round trip."""
    model, thr = exported
    reqs = _trace(3 * SLOTS + 5)
    tracer = Tracer()
    comp, met = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=thr, stage_costs=COSTS,
        tracer=tracer).run_trace(reqs)
    host = [s for s in tracer.spans if s.name.startswith('serve.')]
    assert {s.name for s in host} == set(_HOST)
    assert all(s.track == 'host' for s in host)
    rounds = [s for s in host if s.name == 'serve.round']
    assert len(rounds) == len(met.batches)
    assert [(s.args['stage'], s.args['live']) for s in rounds] == \
        [(k, live) for k, live, _ in met.batches]
    (root,) = [s for s in host if s.name == 'serve.trace']
    assert root.args == {'n_requests': len(reqs)}
    for s in host:
        if s.name in _PARENT:
            assert any(p.name == _PARENT[s.name] and p.t0 <= s.t0
                       and s.t1 <= p.t1 for p in host), s
    for name in ('serve.assemble', 'serve.dispatch', 'serve.land'):
        assert sum(s.name == name for s in host) == len(met.batches)
    doc = tracer.to_chrome()
    procs = {e['pid']: e['args']['name'] for e in doc['traceEvents']
             if e.get('ph') == 'M' and e['name'] == 'process_name'}
    assert 'host' in procs.values()
    spans = load_chrome_trace(doc)
    assert check_trace(spans, comp, strict=True) == []
    got = sorted((s.name, s.track) for s in spans
                 if s.name.startswith('serve.'))
    assert got == sorted((s.name, s.track) for s in host)


@pytest.mark.parametrize('attached', [False, True])
def test_null_tracer_span_records_nothing_but_reaches_the_profiler(
        exported, tmp_path, attached):
    """With no tracer attached the scheduler's host spans are profiler
    annotations only: nothing is recorded, and a profiler session sees
    them on its host plane.  An attached tracer feeds both sinks."""
    model, thr = exported
    with NULL_TRACER.span('serve.round', track='host', stage=0):
        pass
    assert NULL_TRACER.spans == []
    tracer = Tracer() if attached else None
    sched = ContinuousBatchScheduler(model, slots=SLOTS, threshold=thr,
                                     stage_costs=COSTS, tracer=tracer)
    reqs = _trace(SLOTS)
    sched.run_trace(reqs)                  # compile outside the session
    n0 = len(tracer.spans) if attached else 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, met = sched.run_trace(_trace(SLOTS))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob('**/*.xplane.pb')
    names = [e.name for p in jax.profiler.ProfileData.from_file(
        str(path)).planes if p.name == '/host:CPU'
        for line in p.lines for e in line.events]
    assert set(_HOST) <= set(names)
    assert names.count('serve.round') == len(met.batches)
    if attached:
        recorded = [sp.name for sp in tracer.spans[n0:]
                    if sp.name.startswith('serve.')]
        assert sorted(recorded) == sorted(n for n in names
                                          if n.startswith('serve.'))


# ------------------------------------------------------- analysis + gate


def test_analysis_trace_rule_green_and_red():
    from repro import analysis
    from repro.analysis.mutations import MUTANTS
    clean = [Span('stage.exec', 0.0, 0.004, 'replica0',
                  args={'stage': 0, 'live': 8, 'slots': 8, 'rids': [0]})]
    rep = analysis.check(trace=clean, rules=('trace-invariants',))
    assert rep.ok, rep.render()
    assert rep.target == 'trace'
    mut = analysis.check(**MUTANTS['trace-invariants']())
    assert not mut.ok
    errs = [f for f in mut.findings if f.severity == 'error']
    assert len(errs) >= 2, 'both seeded corruptions must be flagged'


# ------------------------------------------------ export kernel profiling


def test_export_measure_mode_emits_kernel_spans():
    fam = CNNFamily(SyntheticImages())
    base = RESNET8_CIFAR
    params = fam.init(jax.random.key(0), base)
    params, _, _ = fam.factorize(params, base, energy=0.6, min_rank=2)
    cfg = base.replace(w_bits=8, a_bits=8)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    tracer = Tracer()
    model = export_cnn(params, cfg, use_pallas=True, calibrate=x,
                      select_kernels='measure', tracer=tracer)
    cal = [s for s in tracer.spans if s.name == 'export.calibrate']
    assert len(cal) == 1 and cal[0].track == 'export'
    assert cal[0].args['select_kernels'] == 'measure'
    launches = [s for s in tracer.spans if s.name == 'kernel.launch']
    assert launches, 'measure mode must time kernels through the tracer'
    assert {s.args['variant'] for s in launches} == {'fused', 'chained'}
    assert all(s.track == 'export' and s.dur >= 0 for s in launches)
    assert check_trace(tracer) == []
    # the measured-vs-modeled delta block rides on the plan summary
    delta = model.plan.summary()['lowering_cost_delta']
    assert delta, 'measure mode must report measured-vs-modeled deltas'
    for d in delta.values():
        assert d['measured_fused_us'] > 0
        # ratios come from the unrounded timings (the us fields are
        # rounded to 0.1us for the JSON), so check sign/consistency only
        assert d['fused_measured_over_modeled'] > 0
        assert d['chained_measured_over_modeled'] > 0
        assert isinstance(d['model_agrees'], bool)
    # model-mode exports carry no delta (nothing was measured)
    model2 = export_cnn(params, cfg, use_pallas=True, calibrate=x)
    assert model2.plan.summary()['lowering_cost_delta'] == {}
