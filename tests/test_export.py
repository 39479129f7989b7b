"""Export-and-serve subsystem tests: the compiled int8 path must match the
fake-quant QAT oracle, compute no per-call weight scales, and the new
quant_conv kernel must match its lax.conv oracle in interpret mode.
The int8-resident plan (``calibrate=...``) additionally must keep
inter-layer activations int8 at every kernel boundary, never run an
activation abs-max, and serve factored conv pairs as single launches."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import prim_count, walk_eqns
from repro.configs.cnn import (MOBILENET_SMALL_CIFAR, RESNET8_CIFAR,
                               VGG8_CIFAR)
from repro.core import quantization as quant_lib
from repro.core.export import early_exit_batch, export_chain, export_cnn
from repro.core.family import CNNFamily
from repro.core.passes import ChainState
from repro.data import SyntheticImages
from repro.kernels import ops, ref
from repro.kernels.quant_conv import im2col_nhwc, quant_conv
from repro.kernels.tiling import (LANE, MODELLED_KIND, device_peaks,
                                  fit_block, fit_or_pad, pad_to)
from repro.models.cnn import cnn_forward, init_cnn

CONFIGS = {'resnet': RESNET8_CIFAR, 'vgg': VGG8_CIFAR,
           'mobilenet': MOBILENET_SMALL_CIFAR}


def _with_exits(base, key=2):
    fam = CNNFamily(SyntheticImages())
    params = fam.init(jax.random.key(0), base)
    params, cfg = fam.add_exits(jax.random.key(key), params, base,
                                fam.default_exit_points(base))
    return fam, params, cfg.replace(w_bits=8, a_bits=8)


# ------------------------------------------------------------ exported path


@pytest.mark.parametrize('kind', sorted(CONFIGS))
def test_export_matches_fake_quant_oracle(kind):
    """Exported int8 serving == fake-quant fp32 forward (same quant grids,
    bilinear kernels) up to fp32 accumulation noise, incl. exit heads."""
    _, params, cfg = _with_exits(CONFIGS[kind])
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    oracle, oracle_exits = jax.jit(
        lambda p, x: cnn_forward(p, cfg, x, collect_exits=True))(params, x)
    model = export_cnn(params, cfg)
    served, served_exits = model.fn_exits(model.params, x)
    scale = float(jnp.max(jnp.abs(oracle)))
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=1e-3 * max(scale, 1.0))
    assert set(served_exits) == set(oracle_exits)
    for s in oracle_exits:
        np.testing.assert_allclose(np.asarray(served_exits[s]),
                                   np.asarray(oracle_exits[s]), atol=1e-3)


def test_export_pallas_matches_jnp_path():
    """Pallas interpret-mode serving == the jnp int8 reference serving."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    m_ref = export_cnn(params, cfg, use_pallas=False)
    m_pls = export_cnn(params, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(m_pls.serve(x)),
                               np.asarray(m_ref.serve(x)),
                               rtol=1e-4, atol=1e-4)


def test_export_low_bit_chain_cfg():
    """Chain-style cfg (w_bits=4, a_bits=8) exports on the 4-bit grid."""
    cfg = VGG8_CIFAR.replace(w_bits=4, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    oracle = jax.jit(lambda p, x: cnn_forward(p, cfg, x))(params, x)
    served = export_cnn(params, cfg).serve(x)
    scale = float(jnp.max(jnp.abs(oracle)))
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=1e-3 * max(scale, 1.0))
    # 4-bit grid: stored int8 values stay within [-8, 7]
    leaves = [v for v in jax.tree_util.tree_leaves(
        export_cnn(params, cfg).params) if v.dtype == jnp.int8]
    assert leaves and all(int(jnp.max(jnp.abs(v))) <= 8 for v in leaves)


def test_export_binary_weights_finite():
    """w_bits=1 (DoReFa sign*mean) exports without inf scales / NaN logits
    — all serving quantizers route through quantize_weight's bits=1
    branch."""
    cfg = VGG8_CIFAR.replace(w_bits=1, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    model = export_cnn(params, cfg)
    served = model.serve(jnp.ones((2, 32, 32, 3)))
    assert bool(jnp.all(jnp.isfinite(served)))
    ints = [v for v in jax.tree_util.tree_leaves(model.params)
            if v.dtype == jnp.int8]
    assert ints and all(int(jnp.max(jnp.abs(v))) <= 1 for v in ints)


def test_export_static_weight_scales():
    """Tracing the serving fn computes NO weight scales; tracing the
    fake-quant forward computes one per weight (the per-call recompute the
    export pass eliminates)."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    model = export_cnn(params, cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))

    before = quant_lib.WEIGHT_SCALE_COMPUTATIONS[0]
    jax.make_jaxpr(lambda x: model.fn(model.params, x))(x)
    assert quant_lib.WEIGHT_SCALE_COMPUTATIONS[0] == before

    jax.make_jaxpr(lambda x: cnn_forward(params, cfg, x))(x)
    assert quant_lib.WEIGHT_SCALE_COMPUTATIONS[0] > before


def test_export_chain_dispatch():
    fam, params, cfg = _with_exits(RESNET8_CIFAR)
    st = ChainState(family=fam, cfg=cfg, params=params,
                    key=jax.random.key(0))
    model = export_chain(st)
    assert model.fn_exits is not None
    out = model.serve(jnp.zeros((2, 32, 32, 3)))
    assert out.shape == (2, cfg.num_classes)


# --------------------------------------------------- low-rank factored path


def _with_factored_exits(base, energy=0.6):
    fam = CNNFamily(SyntheticImages())
    params = fam.init(jax.random.key(0), base)
    params, _, scale = fam.factorize(params, base, energy=energy, min_rank=2)
    assert scale < 1.0                    # something actually factored
    params, cfg = fam.add_exits(jax.random.key(2), params, base,
                                fam.default_exit_points(base))
    return fam, params, cfg.replace(w_bits=8, a_bits=8)


@pytest.mark.parametrize('kind', ['resnet', 'vgg'])
def test_export_factored_matches_fake_quant_oracle(kind):
    """A chain containing 'L' (low-rank u/v conv pairs + factored head fc)
    exports to int8 serving that matches the fake-quant forward — the
    factored dispatch is identical in QAT (models/cnn.py) and serving
    (core/export.py), incl. exit heads hung off factored blocks."""
    _, params, cfg = _with_factored_exits(CONFIGS[kind])
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    oracle, oracle_exits = jax.jit(
        lambda p, x: cnn_forward(p, cfg, x, collect_exits=True))(params, x)
    model = export_cnn(params, cfg)
    served, served_exits = model.fn_exits(model.params, x)
    scale = float(jnp.max(jnp.abs(oracle)))
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=2e-3 * max(scale, 1.0))
    for s in oracle_exits:
        np.testing.assert_allclose(np.asarray(served_exits[s]),
                                   np.asarray(oracle_exits[s]), atol=2e-3)


def test_export_factored_pallas_matches_jnp_path():
    """Factored convs route twice through the kernels: interpret-mode
    Pallas serving == the jnp int8 reference serving."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    fam = CNNFamily(SyntheticImages())
    params = fam.init(jax.random.key(0), cfg)
    params, _, _ = fam.factorize(params, cfg, energy=0.6, min_rank=2)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    m_ref = export_cnn(params, cfg, use_pallas=False)
    m_pls = export_cnn(params, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(m_pls.serve(x)),
                               np.asarray(m_ref.serve(x)),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------- int8-resident serving


# jaxpr walking comes from the shared analyzer walker (repro/analysis) —
# the SAME implementation the production rules enforce contracts with, so
# what these tests count and what the CI gate checks can never drift apart


@pytest.mark.parametrize('kind', sorted(CONFIGS))
def test_export_resident_matches_fake_quant_oracle(kind):
    """The int8-resident plan (static scales, requantize epilogues) tracks
    the fake-quant oracle.  Looser tolerance than the dynamic path: the
    resident graph quantizes conv *outputs* too (that is what keeps them
    int8 in HBM), one extra rounding per layer."""
    _, params, cfg = _with_exits(CONFIGS[kind])
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    oracle, oracle_exits = jax.jit(
        lambda p, x: cnn_forward(p, cfg, x, collect_exits=True))(params, x)
    model = export_cnn(params, cfg, calibrate=x)
    served, served_exits = model.fn_exits(model.params, x)
    scale = float(jnp.max(jnp.abs(oracle)))
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=6e-2 * max(scale, 1.0))
    assert set(served_exits) == set(oracle_exits)
    assert model.summary()['n_layers'] > 0


def test_export_resident_pallas_matches_jnp_path():
    """Interpret-mode Pallas resident serving tracks the jnp resident
    serving.  The backends share every *inter-layer* static grid but differ
    by design inside a layer: Pallas kernels requantize their outputs at
    the HBM boundary, the CPU lowering carries fp32 from conv to its own
    glue (no int8 conv units to feed) — so parity is within per-layer
    quantization noise, not bit-exact."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    m_ref = export_cnn(params, cfg, use_pallas=False, calibrate=x)
    m_pls = export_cnn(params, cfg, use_pallas=True, calibrate=x)
    ref_out = np.asarray(m_ref.serve(x))
    scale = float(np.max(np.abs(ref_out)))
    np.testing.assert_allclose(np.asarray(m_pls.serve(x)), ref_out,
                               atol=4e-2 * max(scale, 1.0))


def test_export_resident_no_dynamic_activation_scales():
    """The resident jaxpr contains ZERO reduce_max ops — no activation
    abs-max ever runs at serve time (weight scales were already static;
    now activation scales are too).  The dynamic path runs one per layer."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))

    m_dyn = export_cnn(params, cfg)
    m_res = export_cnn(params, cfg, calibrate=x)
    dyn = jax.make_jaxpr(lambda x: m_dyn.fn(m_dyn.params, x))(x)
    res = jax.make_jaxpr(lambda x: m_res.fn(m_res.params, x))(x)
    assert prim_count(dyn.jaxpr, 'reduce_max') > 0
    assert prim_count(res.jaxpr, 'reduce_max') == 0

    before = quant_lib.WEIGHT_SCALE_COMPUTATIONS[0]
    jax.make_jaxpr(lambda x: m_res.fn(m_res.params, x))(x)
    assert quant_lib.WEIGHT_SCALE_COMPUTATIONS[0] == before


@pytest.mark.parametrize('kind', sorted(CONFIGS))
def test_export_resident_int8_at_kernel_boundaries(kind):
    """Dtype-trace the resident Pallas serving fn: every kernel consumes
    int8 activations and every kernel output is int8, except the fp32
    logit heads (head + exit fcs).  With the depthwise kernel serving
    mobilenet's grouped convs there is NO fp32 conv left in the graph —
    zero fallback, zero fp32 MACs (the fallback exemption is gone)."""
    _, params, cfg = _with_exits(CONFIGS[kind])
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    model = export_cnn(params, cfg, use_pallas=True, calibrate=x)
    jaxpr = jax.make_jaxpr(
        lambda p, x: model.fn_exits(p, x))(model.params, x)
    calls = [e for e in walk_eqns(jaxpr.jaxpr)
             if e.primitive.name == 'pallas_call']
    assert calls, 'resident export must route through Pallas kernels'
    for e in calls:
        assert e.invars[0].aval.dtype == jnp.int8   # int8 activations in
    out_dtypes = [v.aval.dtype for e in calls for v in e.outvars]
    n_fp32 = sum(1 for d in out_dtypes if d == jnp.float32)
    n_heads = 1 + len(model.cfg.exit_stages)        # final + exit logits
    assert n_fp32 == n_heads, (n_fp32, n_heads)
    assert all(d in (jnp.int8, jnp.float32) for d in out_dtypes)
    # zero fp32 convs in the resident graph — every conv (incl. mobilenet
    # depthwise) runs an int8 Pallas kernel
    assert model.summary()['n_fallback'] == 0
    n_fp32_convs = sum(
        1 for e in walk_eqns(jaxpr.jaxpr)
        if e.primitive.name == 'conv_general_dilated'
        and e.outvars[0].aval.dtype == jnp.float32)
    assert n_fp32_convs == 0, n_fp32_convs


def test_export_resident_factored_single_launch():
    """A factored (u, v) conv layer serves as exactly ONE Pallas launch in
    the resident plan; total pallas_call count matches the plan's
    kernel-launch accounting."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    fam = CNNFamily(SyntheticImages())
    params = fam.init(jax.random.key(0), cfg)
    params, _, _ = fam.factorize(params, cfg, energy=0.6, min_rank=2)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    model = export_cnn(params, cfg, use_pallas=True, calibrate=x)
    s = model.summary()
    assert s['n_fused_lowrank'] > 0
    jaxpr = jax.make_jaxpr(lambda p, x: model.fn(p, x))(model.params, x)
    assert prim_count(jaxpr.jaxpr, 'pallas_call') == s['kernel_launches']
    # exit-head launches are accounted separately: fn excludes them,
    # fn_exits adds exactly that many
    fam2, eparams, ecfg = _with_exits(RESNET8_CIFAR)
    em = export_cnn(eparams, ecfg, use_pallas=True, calibrate=x)
    es = em.summary()
    assert es['n_exit_heads'] == len(ecfg.exit_stages) > 0
    jx_fn = jax.make_jaxpr(lambda p, x: em.fn(p, x))(em.params, x)
    jx_ex = jax.make_jaxpr(lambda p, x: em.fn_exits(p, x))(em.params, x)
    assert prim_count(jx_fn.jaxpr, 'pallas_call') == es['kernel_launches']
    assert prim_count(jx_ex.jaxpr, 'pallas_call') == \
        es['kernel_launches'] + es['exit_head_launches']
    # and the oracle still holds through the fused kernels
    oracle = jax.jit(lambda p, x: cnn_forward(p, cfg, x))(params, x)
    served = export_cnn(params, cfg, use_pallas=False, calibrate=x).serve(x)
    scale = float(jnp.max(jnp.abs(oracle)))
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=6e-2 * max(scale, 1.0))


def test_export_resident_fallback_mac_fraction():
    """Mobilenet's depthwise convs serve on the int8 depthwise kernel now:
    the declared-fallback MAC share the summary used to report (~21%) is
    exactly zero, and the plan counts the layers as depthwise instead."""
    cfg = MOBILENET_SMALL_CIFAR.replace(w_bits=8, a_bits=8)
    params = init_cnn(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    s = export_cnn(params, cfg, calibrate=x).summary()
    assert s['n_fallback'] == 0
    assert s['fallback_mac_fraction'] == 0.0
    assert s['n_depthwise'] > 0
    # resnet has no grouped convs at all
    cfg_r = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    s_r = export_cnn(init_cnn(jax.random.key(0), cfg_r), cfg_r,
                     calibrate=x).summary()
    assert s_r['fallback_mac_fraction'] == 0.0
    assert s_r['n_depthwise'] == 0


def test_export_kernel_selection_recorded():
    """Every factored conv's plan entry records the fused-vs-chained
    decision with costs and a reason; 'model' (default) never contradicts
    the analytic model, 'fused'/fuse_lowrank=False force the lowerings."""
    cfg = RESNET8_CIFAR.replace(w_bits=8, a_bits=8)
    fam = CNNFamily(SyntheticImages())
    params = fam.init(jax.random.key(0), cfg)
    params, _, _ = fam.factorize(params, cfg, energy=0.6, min_rank=2)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
    s = export_cnn(params, cfg, calibrate=x).summary()
    sels = s['lowrank_selection']
    assert sels, 'factored export must record selections'
    for name, sel in sels.items():
        assert sel['choice'] in ('fused', 'chained')
        assert sel['why']
        if 'fused_us' in sel:   # modeled: choice must match the costs
            modeled = ('fused' if sel['fused_us'] <= sel['chained_us']
                       else 'chained')
            assert sel['choice'] == modeled, (name, sel)
    forced = export_cnn(params, cfg, calibrate=x,
                        fuse_lowrank=False).summary()
    assert all(v['choice'] == 'chained'
               for v in forced['lowrank_selection'].values())
    assert forced['n_fused_lowrank'] == 0
    pinned = export_cnn(params, cfg, calibrate=x,
                        select_kernels='fused').summary()
    assert all(v['choice'] == 'fused'
               for v in pinned['lowrank_selection'].values())


def test_export_chain_threads_exit_threshold():
    """export_chain hands the E pass's calibrated operating point to the
    served model, so batch serving exercises ChainState.exit_threshold."""
    fam, params, cfg = _with_exits(RESNET8_CIFAR)
    st = ChainState(family=fam, cfg=cfg, params=params,
                    key=jax.random.key(0), exit_threshold=0.42)
    model = export_chain(st)
    assert model.exit_threshold == 0.42
    x = jax.random.normal(jax.random.key(3), (4, 32, 32, 3))
    pred, stage = model.serve_early_exit(x)     # None -> chain threshold
    assert pred.shape == (4,) and stage.shape == (4,)


# ------------------------------------------------------- batched early exit


def test_early_exit_batch_selection():
    """Earliest confident exit wins; unconfident samples reach the head."""
    logits = jnp.array([[0.0, 5.0], [5.0, 0.0], [0.0, 5.0]])
    exits = {
        0: jnp.array([[9.0, 0.0], [0.1, 0.0], [0.0, 0.1]]),   # conf, no, no
        1: jnp.array([[0.0, 9.0], [9.0, 0.0], [0.1, 0.0]]),   # conf, conf, no
    }
    pred, stage = early_exit_batch(logits, exits, threshold=0.9)
    np.testing.assert_array_equal(np.asarray(stage), [0, 1, -1])
    np.testing.assert_array_equal(np.asarray(pred), [0, 0, 1])


def test_serve_early_exit_runs_batched():
    _, params, cfg = _with_exits(RESNET8_CIFAR)
    model = export_cnn(params, cfg)
    x = jax.random.normal(jax.random.key(3), (16, 32, 32, 3))
    pred, stage = model.serve_early_exit(x, threshold=0.5)
    assert pred.shape == (16,) and stage.shape == (16,)
    assert bool(jnp.all((stage >= -1)
                        & (stage < len(cfg.stage_blocks))))


# ----------------------------------------------------------- quant_conv


@pytest.mark.parametrize('stride,relu', [(1, False), (2, False), (1, True)])
def test_quant_conv_matches_lax_conv_oracle(stride, relu):
    """Pallas quant_conv (interpret) == lax.conv on dequantized operands."""
    k = jax.random.key(0)
    x = jax.random.normal(k, (2, 8, 8, 16))
    w = jax.random.normal(jax.random.fold_in(k, 1), (3, 3, 16, 32)) * 0.1
    b = jax.random.normal(jax.random.fold_in(k, 2), (32,))
    w_q, sw = ops.prequantize_weight(w)
    x_q, sx = ops.quantize_act(x)
    out = quant_conv(x_q, w_q, sx, sw, b, stride=stride, relu=relu,
                     interpret=True)
    expect = ref.quant_conv_ref(x_q, w_q, sx, sw, b, stride=stride,
                                relu=relu)
    assert out.shape == expect.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_quant_conv_1x1_and_no_bias():
    k = jax.random.key(5)
    x = jax.random.normal(k, (2, 8, 8, 8))
    w = jax.random.normal(jax.random.fold_in(k, 1), (1, 1, 8, 16)) * 0.2
    w_q, sw = ops.prequantize_weight(w)
    x_q, sx = ops.quantize_act(x)
    out = quant_conv(x_q, w_q, sx, sw, stride=2, interpret=True)
    expect = ref.quant_conv_ref(x_q, w_q, sx, sw, stride=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def _scopes(hlo_text):
    """The layer scopes among compiled ops' ``op_name`` metadata paths."""
    return set(re.findall(r'op_name="[^"]*?/(s0b0\.conv1|im2col)/',
                          hlo_text))


def test_export_segment_ops_carry_layer_scopes(monkeypatch):
    """A segment program's ops carry their layer's name scope (the conv's
    stable name, and ``im2col`` for its patch gather), and the scopes are
    metadata only: the same plan lowered without them answers bit for
    bit the same."""
    from repro.core import export as export_lib
    _, params, cfg = _with_exits(RESNET8_CIFAR)
    x = jax.random.normal(jax.random.key(3), (8, 16, 16, 3))
    model = export_cnn(params, cfg, use_pallas=True, calibrate=x)
    fn = model.stage_fns[0]
    assert _scopes(fn.lower(model.params, x).compile().as_text()) == {
        's0b0.conv1', 'im2col'}
    want = jax.block_until_ready(fn(model.params, x))
    conv_fn, fc_fn, glue_fn, pool_fn = export_lib._resident_layers(
        model.plan, True, qparams=model.params)
    monkeypatch.setattr(jax, 'named_scope',
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()                 # retrace the kernels' inner jits
    try:
        (bare, *_), _ = export_lib._make_stage_fns(
            cfg, dict(conv_fn=conv_fn, fc_fn=fc_fn, glue_fn=glue_fn,
                      pool_fn=pool_fn))
        assert _scopes(bare.lower(model.params, x).compile()
                       .as_text()) == set()
        got = jax.block_until_ready(bare(model.params, x))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    (w_exits, w_carry), (g_exits, g_carry) = want, got
    assert set(w_exits) == set(g_exits)
    for s in w_exits:
        np.testing.assert_array_equal(np.asarray(g_exits[s]),
                                      np.asarray(w_exits[s]))
    assert g_carry.scale == w_carry.scale
    np.testing.assert_array_equal(np.asarray(g_carry.q),
                                  np.asarray(w_carry.q))


def test_im2col_matches_conv_patches():
    """im2col patch matrix @ flat weights == SAME lax.conv, fp32."""
    k = jax.random.key(7)
    for stride in (1, 2):
        x = jax.random.normal(k, (2, 7, 9, 5))
        w = jax.random.normal(jax.random.fold_in(k, 1), (3, 3, 5, 4))
        patches, (oh, ow) = im2col_nhwc(x, 3, 3, stride)
        got = (patches @ w.reshape(-1, 4)).reshape(2, oh, ow, 4)
        expect = jax.lax.conv_general_dilated(
            x, w, (stride, stride), 'SAME',
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------- ops split + shared tiling


def test_prequantize_plus_quant_dense_equals_wrapper():
    k = jax.random.key(0)
    x = jax.random.normal(k, (32, 128))
    w = jax.random.normal(jax.random.fold_in(k, 1), (128, 64)) * 0.1
    w_q, sw = ops.prequantize_weight(w)
    a = ops.quant_dense(x, w_q, sw)
    b = ops.quantize_dense_int8(x, w)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    rel = float(jnp.max(jnp.abs(a - x @ w)) / jnp.max(jnp.abs(x @ w)))
    assert rel < 0.02, rel


def test_fake_quant_fused_matches_two_pass():
    w = jax.random.normal(jax.random.key(0), (256, 192))
    fused = ops.fake_quant(w, 8, fused=True)
    two = ops.fake_quant(w, 8, fused=False)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(ref.fake_quant_ref(w, 8)),
                               rtol=1e-5, atol=1e-6)


def test_fake_quant_weight_kernel_path_matches_jnp():
    """The QAT hot-path wiring: kernel-backed fake_quant_weight == the jnp
    grid, and the STE gradient stays identity (no VJP through Pallas)."""
    from repro.core.quantization import fake_quant_weight
    w = jax.random.normal(jax.random.key(0), (128, 96))
    jnp_out = fake_quant_weight(w, 8, use_kernel=False)
    krn_out = fake_quant_weight(w, 8, use_kernel=True)
    np.testing.assert_allclose(np.asarray(krn_out), np.asarray(jnp_out),
                               rtol=1e-5, atol=1e-6)
    g = jax.grad(lambda w: jnp.sum(fake_quant_weight(w, 8,
                                                     use_kernel=True)))(w)
    np.testing.assert_allclose(np.asarray(g), np.ones_like(w), rtol=1e-6)


def test_tiling_fit_block_and_padding():
    assert fit_block(128, 256) == 128
    assert fit_block(128, 96) == 96
    assert fit_block(128, 97) == 97              # dim fits in one block: fine
    with pytest.raises(ValueError, match='pad the dim'):
        fit_block(64, 97)                        # prime: no silent 1-blocks
    assert fit_or_pad(64, 97) == (64, 128)
    assert pad_to(97) == 128 and pad_to(128) == 128


@pytest.mark.parametrize('block,dim,want', [
    (256, 576, (128, 640)),      # 3x3x64 im2col K: never the 192 Mosaic refuses
    (256, 1152, (128, 1152)),    # 3x3x128: a 128-multiple divisor exists
    (256, 512, (256, 512)),
    (128, 10, (10, 10)),         # whole dim in one block is always legal
])
def test_tiling_lane_blocks_are_128_aligned(block, dim, want):
    """Mosaic's rule for a lane-axis block: a multiple of 128 or the whole
    dim.  fit_or_pad(align=LANE) pads instead of picking anything else."""
    assert fit_or_pad(block, dim, align=LANE) == want
    b, p = want
    assert b == p or b % LANE == 0


def test_device_peaks_table():
    """One sourced peak table keyed by device_kind: the CPU gets the v5e
    row labelled as modelled, an unknown TPU kind is an error."""
    import types
    row = device_peaks(jax.devices('cpu')[0])
    assert row['modelled'] and row['kind'] == MODELLED_KIND
    assert row['int8_ops'] == 2 * row['bf16_flops']
    v5e = device_peaks(types.SimpleNamespace(platform='tpu',
                                             device_kind='TPU v5 lite'))
    assert not v5e['modelled'] and v5e['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError, match='no published peaks'):
        device_peaks(types.SimpleNamespace(platform='tpu',
                                           device_kind='TPU v99'))


def test_prime_dims_pad_through_kernels():
    """Prime dims LARGER than the block no longer degrade to 1-wide blocks
    — the kernels zero-pad to the next 128 multiple and slice back.  Dims
    like 257/131/139 with 128 blocks force the pad branch (fit_or_pad must
    pad all three: no divisor of a prime > block exceeds the floor)."""
    k = jax.random.key(0)
    M, K, N = 257, 131, 139
    assert fit_or_pad(128, M)[1] > M           # the pad branch is live
    xq = jax.random.randint(k, (M, K), -128, 128, jnp.int8)
    wq = jax.random.randint(jax.random.fold_in(k, 1), (K, N), -128, 128,
                            jnp.int8)
    sx = jnp.full((M,), 0.01)
    sw = jnp.full((N,), 0.02)
    b = jax.random.normal(k, (N,))
    from repro.kernels.quant_matmul import quant_matmul
    out = quant_matmul(xq, wq, sx, sw, b, bm=128, bn=128, bk=128,
                       relu=True, interpret=True)
    expect = jnp.maximum(ref.quant_matmul_ref(xq, wq, sx, sw) + b, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    from repro.kernels.fake_quant import fake_quant
    w = jax.random.normal(k, (257, 131))
    np.testing.assert_allclose(
        np.asarray(fake_quant(w, bits=8, bk=128, bn=128, interpret=True)),
        np.asarray(ref.fake_quant_ref(w, 8)), rtol=1e-5, atol=1e-6)
