"""Serving-runtime subsystem tests: the stage-resumable export must be
bit-exact vs the monolithic serving fn (and account its kernel launches),
the continuous-batching scheduler must drain any trace with per-request
answers bit-exact vs the request-alone oracle at fixed slot geometry, and
ChainState must round-trip through checkpoint/chain_io.py so the model
registry can load what Pipeline.run persisted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.cnn import RESNET8_CIFAR
from repro.core.export import (QAct, calibrate_exit_threshold, export_cnn,
                               export_chain)
from repro.core.family import CNNFamily
from repro.core.passes import ChainState
from repro.data import SyntheticImages
from repro.kernels.tiling import batch_slots
from repro.serving import (Completion, ContinuousBatchScheduler,
                           ModelRegistry, Request, RequestQueue,
                           ServingMetrics, StaticBatchScheduler,
                           exit_decisions, percentile)

SLOTS = 8


@pytest.fixture(scope='module')
def family():
    return CNNFamily(SyntheticImages())


@pytest.fixture(scope='module')
def exported(family):
    """Int8-resident export with exit heads (the scheduler's contract)."""
    base = RESNET8_CIFAR
    params = family.init(jax.random.key(0), base)
    params, cfg = family.add_exits(jax.random.key(2), params, base,
                                   family.default_exit_points(base))
    cfg = cfg.replace(w_bits=8, a_bits=8)
    calib = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    return export_cnn(params, cfg, calibrate=calib), cfg


def _trace(n, rate=2000.0, seed=0):
    xs = jax.random.normal(jax.random.key(11), (max(n, 1), 32, 32, 3))
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return [Request(i, xs[i], float(t[i])) for i in range(n)]


def _oracle(model, x, threshold):
    """Monolithic fn_exits on the request ALONE at the slot geometry."""
    xb = jnp.concatenate([x[None],
                          jnp.zeros((SLOTS - 1,) + x.shape, x.dtype)])
    logits, exits = model.fn_exits(model.params, xb)
    stage, ans = exit_decisions(logits, exits, threshold)
    return int(stage[0]), ans[0]


# -------------------------------------------------- stage-resumable export


def test_stage_split_bit_exact_vs_monolithic(exported):
    model, cfg = exported
    assert model.n_stages == len(cfg.exit_stages) + 1
    x = jax.random.normal(jax.random.key(5), (SLOTS, 32, 32, 3))
    logits, exits = model.fn_exits(model.params, x)
    s_logits, s_exits = model.serve_stages(x)
    assert set(s_exits) == set(exits)
    for s in exits:
        np.testing.assert_array_equal(np.asarray(s_exits[s]),
                                      np.asarray(exits[s]))
    np.testing.assert_array_equal(np.asarray(s_logits), np.asarray(logits))


def test_stage_carry_is_int8_on_resident_plan(exported):
    model, _ = exported
    x = jax.random.normal(jax.random.key(5), (SLOTS, 32, 32, 3))
    carry = x
    for k in range(model.n_stages - 1):
        _, carry = model.run_stage(k, carry)
        assert isinstance(carry, QAct), 'resident carry must stay QAct'
        assert carry.q.dtype == jnp.int8
        assert isinstance(carry.scale, float)


def test_stage_split_launch_count(exported):
    """Sum of pallas_call launches across the stage segments == the
    monolithic fn_exits launch count: the split re-partitions the layer
    plan, it must not add or drop kernel launches."""
    _, cfg = exported
    params = CNNFamily(SyntheticImages()).init(jax.random.key(0),
                                               RESNET8_CIFAR)
    params, cfg = CNNFamily(SyntheticImages()).add_exits(
        jax.random.key(2), params, RESNET8_CIFAR,
        (0, 1))
    cfg = cfg.replace(w_bits=8, a_bits=8)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    model = export_cnn(params, cfg, use_pallas=True, calibrate=x)

    def _count(jaxpr):
        n = 0
        for e in jaxpr.eqns:
            n += e.primitive.name == 'pallas_call'
            for v in e.params.values():
                if hasattr(v, 'jaxpr'):
                    n += _count(v.jaxpr)
                elif hasattr(v, 'eqns'):
                    n += _count(v)
        return n

    mono = _count(jax.make_jaxpr(
        lambda p, x: model.fn_exits(p, x))(model.params, x).jaxpr)
    carry, total = x, 0
    for k in range(model.n_stages):
        jx = jax.make_jaxpr(
            lambda p, h, _k=k: model.stage_fns[_k](p, h))(model.params,
                                                          carry)
        total += _count(jx.jaxpr)
        if k < model.n_stages - 1:
            _, carry = model.run_stage(k, carry)
    assert total == mono > 0


def test_run_stage_requires_exit_heads():
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    params = CNNFamily(SyntheticImages()).init(jax.random.key(0), cfg)
    model = export_cnn(params, cfg)
    assert model.n_stages == 0
    with pytest.raises(ValueError, match='without exit heads'):
        model.run_stage(0, jnp.zeros((1, 32, 32, 3)))
    with pytest.raises(ValueError, match='exit boundaries'):
        ContinuousBatchScheduler(model, slots=SLOTS)


# ------------------------------------------------------ batched early exit


def test_serve_early_exit_empty_batch(exported):
    model, _ = exported
    pred, stage = model.serve_early_exit(jnp.zeros((0, 32, 32, 3)))
    assert pred.shape == (0,) and stage.shape == (0,)


def test_serve_early_exit_threshold_none_uses_calibrated(exported):
    model, _ = exported
    x = jax.random.normal(jax.random.key(9), (SLOTS, 32, 32, 3))
    model.exit_threshold = 2.0            # impossible: nothing exits
    try:
        _, stage = model.serve_early_exit(x)
        assert bool(jnp.all(stage == -1))
        model.exit_threshold = -1.0       # everything exits at head 1
        _, stage = model.serve_early_exit(x)
        assert bool(jnp.all(stage == min(model.cfg.exit_stages)))
    finally:
        model.exit_threshold = 0.9


def test_scheduler_all_exit_and_none_exit(exported):
    model, cfg = exported
    reqs = _trace(2 * SLOTS)
    # threshold 2.0: nobody exits — every request runs all segments
    comp, met = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=2.0,
        stage_costs=[1e-3] * model.n_stages).run_trace(reqs)
    assert len(comp) == len(reqs)
    assert all(c.exit_stage == -1 for c in comp.values())
    s = met.summary()
    assert s['exit_fraction'] == 0.0
    assert all(str(k) in s['n_batches'] for k in range(model.n_stages))
    # threshold -1.0: everyone exits at the FIRST head; deeper segments
    # never execute (the compute early exit is supposed to save)
    comp, met = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=-1.0,
        stage_costs=[1e-3] * model.n_stages).run_trace(reqs)
    first = min(cfg.exit_stages)
    assert all(c.exit_stage == first for c in comp.values())
    s = met.summary()
    assert s['exit_fraction'] == 1.0
    assert set(s['n_batches']) == {'0'}, 'later segments must not run'


def test_scheduler_empty_trace(exported):
    model, _ = exported
    comp, met = ContinuousBatchScheduler(
        model, slots=SLOTS,
        stage_costs=[1e-3] * model.n_stages).run_trace([])
    assert comp == {}
    assert met.summary()['n_requests'] == 0


def test_scheduler_threshold_none_falls_back_to_model(exported):
    model, _ = exported
    model.exit_threshold = 2.0
    try:
        sched = ContinuousBatchScheduler(model, slots=SLOTS)
        assert sched.threshold == 2.0
    finally:
        model.exit_threshold = 0.9


def test_scheduler_drains_and_matches_request_alone_oracle(exported):
    """The tentpole contract: under a Poisson trace with compaction and
    backfill, every request's answer (exit stage AND logits) is bit-exact
    vs the monolithic model serving that request alone at the same slot
    geometry — batch composition never leaks into results."""
    model, _ = exported
    x8 = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    thr = calibrate_exit_threshold(model, x8)
    reqs = _trace(3 * SLOTS + 5)          # partial final batch too
    sched = ContinuousBatchScheduler(model, slots=SLOTS, threshold=thr,
                                     stage_costs=[1e-3] * model.n_stages)
    comp, met = sched.run_trace(reqs)
    assert len(comp) == len(reqs), 'queue not drained'
    for r in reqs:
        stage, ans = _oracle(model, r.x, thr)
        assert comp[r.rid].exit_stage == stage
        np.testing.assert_array_equal(comp[r.rid].logits, ans)
        assert comp[r.rid].pred == int(ans.argmax())
        assert comp[r.rid].latency >= 0.0
    s = met.summary()
    assert s['n_requests'] == len(reqs)
    assert 0.0 < s['exit_fraction'] <= 1.0
    assert s['throughput_rps'] > 0


def test_static_scheduler_agrees_with_compacting(exported):
    model, _ = exported
    x8 = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    thr = calibrate_exit_threshold(model, x8)
    reqs = _trace(2 * SLOTS)
    c_comp, _ = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=thr,
        stage_costs=[1e-3] * model.n_stages).run_trace(reqs)
    s_comp, _ = StaticBatchScheduler(
        model, slots=SLOTS, threshold=thr, batch_cost=3e-3).run_trace(reqs)
    for r in reqs:
        assert c_comp[r.rid].exit_stage == s_comp[r.rid].exit_stage
        np.testing.assert_array_equal(c_comp[r.rid].logits,
                                      s_comp[r.rid].logits)


def test_scheduler_wall_clock_mode(exported):
    """stage_costs=None times real executions; latencies stay ordered."""
    model, _ = exported
    reqs = _trace(SLOTS)
    comp, _ = ContinuousBatchScheduler(model,
                                       slots=SLOTS).run_trace(reqs)
    assert len(comp) == SLOTS
    assert all(c.t_done >= c.t_arrival for c in comp.values())


# ----------------------------------------------------- queue and metrics


def test_request_queue_time_gated():
    q = RequestQueue([Request(0, None, 0.0), Request(1, None, 1.0),
                      Request(2, None, 2.0)])
    assert q.pop_ready(0.5, 8) == [Request(0, None, 0.0)]
    assert q.next_arrival() == 1.0
    assert [r.rid for r in q.pop_ready(5.0, 1)] == [1]
    with pytest.raises(ValueError, match='arrival order'):
        q.push(Request(3, None, 0.5))
    assert len(q) == 1


def test_metrics_percentiles_and_occupancy():
    m = ServingMetrics()
    for i, lat in enumerate([0.01, 0.02, 0.03, 0.04]):
        m.record_completion(Completion(rid=i, logits=None, pred=0,
                                       exit_stage=(0 if i < 3 else -1),
                                       t_arrival=0.0, t_done=lat))
    m.record_batch(0, 4, 8)
    m.record_batch(1, 2, 8)
    s = m.summary()
    assert s['p50_latency_s'] == pytest.approx(0.025)
    assert s['p99_latency_s'] == pytest.approx(percentile(
        [0.01, 0.02, 0.03, 0.04], 99))
    assert s['exit_fraction'] == 0.75
    assert s['batch_occupancy'] == {'0': 0.5, '1': 0.25}
    assert percentile([], 99) == 0.0


def test_gather_rows_counts_uploads_and_takes():
    """What ``_gather_rows`` issues: one upload for a run of fresh host
    rows (a batch of them padded on the host, so its bytes are the whole
    batch's), none for a row already on the device, and per survivor
    source batch one index upload and one take per leaf."""
    from repro.serving.scheduler import _gather_rows
    rows = np.random.default_rng(0).normal(size=(SLOTS, 4, 4, 3)).astype(
        np.float32)
    batch, tr = _gather_rows([(r, None) for r in rows], SLOTS)
    assert tr == {'n_uploads': 1, 'upload_bytes': rows.nbytes,
                  'n_takes': 0}
    np.testing.assert_array_equal(np.asarray(batch), rows)
    _, tr = _gather_rows([(rows[0], None)], SLOTS)
    assert tr == {'n_uploads': 1, 'upload_bytes': rows.nbytes,
                  'n_takes': 0}
    _, tr = _gather_rows([(jnp.asarray(rows[0]), None)], SLOTS)
    assert tr == {'n_uploads': 0, 'upload_bytes': 0, 'n_takes': 0}
    a, b = jnp.asarray(rows), QAct(jnp.asarray(rows, jnp.int8), 0.5)
    c = jnp.asarray(rows) + 1
    batch, tr = _gather_rows([(a, 0), (a, 3), (c, 1), (c, 2), (c, 5)],
                             SLOTS)
    assert tr == {'n_uploads': 2, 'upload_bytes': 5 * 4, 'n_takes': 2}
    np.testing.assert_array_equal(
        np.asarray(batch)[:5],
        np.concatenate([rows[[0, 3]], rows[[1, 2, 5]] + 1]))
    assert not np.asarray(batch)[5:].any()              # the pad
    batch, tr = _gather_rows([(b, 1), (b, 2)], SLOTS)
    assert isinstance(batch, QAct) and batch.scale == 0.5
    assert tr == {'n_uploads': 1, 'upload_bytes': 2 * 4, 'n_takes': 1}


@pytest.mark.parametrize('kinds', ['h' * SLOTS, 'hhh', 'hhdh', 'dhhd',
                                   'htthh'])
def test_gather_rows_uploads_each_host_run_once(kinds):
    """Row ``i`` of the batch is source ``i``: a fresh host row ('h'), a
    fresh device row ('d') or a survivor take from one device batch
    ('t').  Each run of host rows uploads once; a batch of host rows
    alone comes back zero-padded to ``slots`` from the host, bit for bit
    ``np.stack`` of its rows; mixed batches keep the sources' order."""
    from repro.serving.scheduler import _gather_rows
    rows = np.random.default_rng(1).normal(size=(SLOTS, 4, 4, 3)).astype(
        np.float32)
    dev = jnp.asarray(rows)
    src = {'h': lambda i: (rows[i], None),
           'd': lambda i: (jnp.asarray(rows[i]), None),
           't': lambda i: (dev, i)}
    batch, tr = _gather_rows([src[k](i) for i, k in enumerate(kinds)],
                             SLOTS)
    assert isinstance(batch, jax.Array) and batch.shape == rows.shape
    out = np.asarray(batch)
    n = len(kinds)
    np.testing.assert_array_equal(out[:n], np.stack(rows[:n]))
    assert not out[n:].any()
    runs = [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]
    host_rows = SLOTS if set(kinds) == {'h'} else kinds.count('h')
    assert tr == {'n_uploads': runs.count('h') + runs.count('t'),
                  'upload_bytes': host_rows * rows[0].nbytes
                  + kinds.count('t') * 4,
                  'n_takes': runs.count('t')}


def test_scheduler_transfer_counters_follow_batch_composition(exported):
    """A segment-0 batch of fresh host rows uploads once; with no exits
    every later batch gathers one source batch's survivors: one index
    upload and one take.  The totals ride on the run's metrics."""
    model, _ = exported
    rows = np.asarray(jax.random.normal(jax.random.key(9),
                                        (2 * SLOTS, 32, 32, 3)))
    reqs = [Request(i, rows[i], 0.0) for i in range(2 * SLOTS)]
    _, met = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=2.0,
        stage_costs=[1e-3] * model.n_stages).run_trace(reqs)
    later = 2 * (model.n_stages - 1)
    s = met.summary()
    assert s['n_batches'] == {str(k): 2 for k in range(model.n_stages)}
    assert s['n_uploads'] == 2 + later
    assert s['upload_bytes'] == rows.nbytes + later * SLOTS * 4
    assert s['n_takes'] == later


def test_scheduler_host_rows_match_request_alone_oracle(exported):
    """Fresh host ``numpy`` rows, stacked and uploaded per batch, answer
    bit for bit as the monolithic ``fn_exits`` on each request alone,
    partial final batch included."""
    model, _ = exported
    x8 = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    thr = calibrate_exit_threshold(model, x8)
    reqs = [Request(r.rid, np.asarray(r.x), r.t_arrival)
            for r in _trace(2 * SLOTS + 3)]
    comp, _ = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=thr,
        stage_costs=[1e-3] * model.n_stages).run_trace(reqs)
    assert len(comp) == len(reqs)
    for r in reqs:
        stage, ans = _oracle(model, r.x, thr)
        assert comp[r.rid].exit_stage == stage
        np.testing.assert_array_equal(comp[r.rid].logits, ans)


def test_latency_splits_into_queue_wait_and_execute(exported):
    """Both schedulers stamp Completion.t_start at first dispatch, so
    every latency decomposes exactly into queue-wait + execute and the
    summary reports both percentile families."""
    model, _ = exported
    reqs = _trace(2 * SLOTS, rate=5000.0)
    costs = [4e-3, 2e-3, 1e-3]
    for sched in (ContinuousBatchScheduler(model, slots=SLOTS,
                                           stage_costs=costs),
                  StaticBatchScheduler(model, slots=SLOTS,
                                       batch_cost=sum(costs))):
        comp, met = sched.run_trace(reqs)
        assert len(comp) == len(reqs)
        for c in comp.values():
            assert c.t_start is not None
            assert c.t_arrival <= c.t_start <= c.t_done
            assert c.queue_wait + c.execute == pytest.approx(c.latency)
        s = met.summary()
        for key in ('p50_queue_wait_s', 'p99_queue_wait_s',
                    'p50_execute_s', 'p99_execute_s'):
            assert s[key] >= 0.0
        assert s['p50_queue_wait_s'] + s['p50_execute_s'] > 0.0
        # on the simulated clock execute time is bounded by full depth
        assert s['p99_execute_s'] <= sum(costs) + 1e-9
    # a queue backlog shows up in queue-wait, not execute: the 2nd batch
    # of a near-simultaneous burst waits for the 1st
    burst = _trace(2 * SLOTS, rate=10 ** 6)
    _, met = ContinuousBatchScheduler(model, slots=SLOTS,
                                      stage_costs=costs).run_trace(burst)
    assert met.summary()['p99_queue_wait_s'] >= costs[0]


def test_batch_slots_geometry():
    assert batch_slots(1) == 8
    assert batch_slots(8) == 8
    assert batch_slots(9) == 16
    assert batch_slots(0) == 8            # never an empty geometry
    assert batch_slots(33, mult=8) == 40


# --------------------------------------- checkpointing + model registry


def _chain_state(family, with_factored=True):
    base = RESNET8_CIFAR
    params = family.init(jax.random.key(0), base)
    if with_factored:
        params, _, _ = family.factorize(params, base, energy=0.6,
                                        min_rank=2)
    params, cfg = family.add_exits(jax.random.key(2), params, base,
                                   family.default_exit_points(base))
    cfg = cfg.replace(w_bits=8, a_bits=8)
    return ChainState(family=family, cfg=cfg, params=params,
                      key=jax.random.key(7), base_bitops=1e9, base_bits=2e6,
                      prune_scale=0.7, lowrank_scale=0.5,
                      exit_probs={0: 0.25, 1: 0.5}, exit_threshold=0.42,
                      dyn_accuracy=0.5,
                      history=[{'pass': 'baseline', 'acc': 0.5}])


def test_chain_state_checkpoint_roundtrip(family, tmp_path):
    from repro.checkpoint import load_chain_state, save_chain_state
    st = _chain_state(family)
    save_chain_state(str(tmp_path), st, step=2)
    got, step = load_chain_state(str(tmp_path), family)
    assert step == 2
    assert got.cfg == st.cfg
    assert got.exit_threshold == 0.42
    assert got.exit_probs == {0: 0.25, 1: 0.5}
    assert got.mac_scale == pytest.approx(st.mac_scale)
    assert got.history == st.history
    assert np.array_equal(jax.random.key_data(got.key),
                          jax.random.key_data(st.key))
    a = jax.tree_util.tree_leaves(st.params)
    b = jax.tree_util.tree_leaves(got.params)
    assert len(a) == len(b)               # factored {'u','v'} trees survive
    assert all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(a, b))
    # the round-tripped state serves identically
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    np.testing.assert_array_equal(
        np.asarray(family.logits(st.params, st.cfg, x)),
        np.asarray(family.logits(got.params, got.cfg, x)))


def test_pipeline_checkpoint_resume(family, tmp_path):
    """Pipeline.run(checkpoint_dir=...) persists after every pass and a
    re-run resumes from disk instead of re-applying passes."""
    from repro.checkpoint.manager import latest_step
    from repro.core import registry
    from repro.core.chain import Pipeline
    from repro.core.passes import Trainer

    applied = []
    orig = registry.get_pass('Q')

    def counting_q(state, hp, trainer):
        applied.append('Q')
        return orig.fn(state, hp, trainer)

    fast = Trainer(batch=8, steps=1, eval_n=1, eval_batch=16)
    st0 = _chain_state(family, with_factored=False)
    registry.unregister('Q')
    registry.register(registry.CompressionPass(
        'Q', orig.name, orig.kind, orig.granularity, orig.hp_cls,
        counting_q))
    try:
        pipe = Pipeline.from_sequence('Q')
        out = pipe.run(family, st0.cfg, fast, state=st0,
                       checkpoint_dir=str(tmp_path))
        assert applied == ['Q']
        assert latest_step(str(tmp_path)) == 1
        # resume: the pass is already on disk, fn must NOT run again
        out2 = pipe.run(family, st0.cfg, fast,
                        checkpoint_dir=str(tmp_path))
        assert applied == ['Q']
        np.testing.assert_array_equal(
            np.asarray(jax.tree_util.tree_leaves(out.params)[0]),
            np.asarray(jax.tree_util.tree_leaves(out2.params)[0]))
        # a DIFFERENT pipeline must refuse this checkpoint, not silently
        # skip passes it never ran
        with pytest.raises(ValueError, match='produced by passes'):
            Pipeline.from_sequence('E').run(family, st0.cfg, fast,
                                            checkpoint_dir=str(tmp_path))
    finally:
        registry.unregister('Q')
        registry.register(orig)


def test_model_registry_loads_checkpointed_chain(family, tmp_path):
    from repro.checkpoint import save_chain_state
    st = _chain_state(family)
    save_chain_state(str(tmp_path), st, step=0)
    reg = ModelRegistry()
    calib = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    model = reg.load('resnet8', str(tmp_path), family, calibrate=calib)
    assert 'resnet8' in reg and reg.names() == ['resnet8']
    assert reg.get('resnet8') is model
    assert model.exit_threshold == 0.42   # chain threshold threaded through
    assert model.n_stages == len(st.cfg.exit_stages) + 1
    # a registry-loaded model drives the scheduler end to end
    comp, _ = ContinuousBatchScheduler(
        model, slots=SLOTS, threshold=calibrate_exit_threshold(model, calib),
        stage_costs=[1e-3] * model.n_stages).run_trace(_trace(SLOTS))
    assert len(comp) == SLOTS
    with pytest.raises(ValueError, match='already registered'):
        reg.register('resnet8', model)
    with pytest.raises(KeyError):
        reg.get('missing')


def test_export_chain_stage_fns_from_state(family):
    """export_chain gives the registry path the same stage-split API."""
    st = _chain_state(family, with_factored=False)
    calib = jax.random.normal(jax.random.key(3), (SLOTS, 32, 32, 3))
    model = export_chain(st, calibrate=calib)
    assert model.n_stages == len(st.cfg.exit_stages) + 1
    x = jax.random.normal(jax.random.key(5), (SLOTS, 32, 32, 3))
    logits, _ = model.fn_exits(model.params, x)
    s_logits, _ = model.serve_stages(x)
    np.testing.assert_array_equal(np.asarray(s_logits), np.asarray(logits))
