"""The served Pallas kernels compile for a TPU v5e, at the widths the
paper's models use (resnet34-cifar / mobilenetv2-cifar), batch 128.

Interpret mode (every other kernel test) runs block shapes and in-kernel
slices that Mosaic refuses, so these cases hand each kernel to the TPU
compiler for one *described* v5e chip — nothing runs, no chip is needed.
A case passes when the compile succeeds and the program holds the kernel
as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.depthwise_conv import depthwise_conv
from repro.kernels.fake_quant import fake_quant_fused
from repro.kernels.lowrank_conv import lowrank_conv
from repro.kernels.quant_conv import quant_conv
from repro.kernels.quant_matmul import quant_matmul

B = 128


@pytest.fixture(scope='module')
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv('TPU_LOG_DIR', 'disabled')
        from jax.experimental import topologies
        try:
            t = topologies.get_topology_desc(platform='tpu',
                                             topology_name='v5e:2x2')
        except Exception as e:   # no TPU compiler here
            pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
        yield t


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module')
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', before)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    specs = [_spec(sharding, s, d) for s, d in shapes]
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert 'tpu_custom_call' in text, 'no Mosaic kernel in the program'
    return text


# (H, CIN, COUT, stride): resnet34-cifar stem, stage-0 conv, and the
# stage-3 downsampling conv — the requantize epilogue the resident plan runs
QUANT_CONV = {'3to64': (32, 3, 64, 1), '64to64_s1': (32, 64, 64, 1),
              '256to512_s2': (8, 256, 512, 2)}


@pytest.mark.parametrize('case', sorted(QUANT_CONV))
def test_quant_conv_compiles_for_v5e(case, one_chip, no_compile_cache):
    h, cin, cout, stride = QUANT_CONV[case]
    fn = functools.partial(quant_conv, stride=stride, out_scale=0.05)
    _compile(fn, one_chip, ((B, h, h, cin), jnp.int8),
             ((3, 3, cin, cout), jnp.int8), ((), jnp.float32),
             ((cout,), jnp.float32), ((cout,), jnp.float32))


def test_quant_matmul_head_compiles_for_v5e(one_chip, no_compile_cache):
    """The 512 -> 10 logit head: fp32 out, per-tensor scale, bias."""
    _compile(quant_matmul, one_chip, ((B, 512), jnp.int8),
             ((512, 10), jnp.int8), ((), jnp.float32), ((10,), jnp.float32),
             ((10,), jnp.float32))


def test_lowrank_conv_compiles_for_v5e(one_chip, no_compile_cache):
    """64 -> r32 -> 64 fused factored conv at 32x32."""
    fn = functools.partial(lowrank_conv, sx=0.02, h_scale=0.03,
                           out_scale=0.05)
    _compile(fn, one_chip, ((B, 32, 32, 64), jnp.int8),
             ((3, 3, 64, 32), jnp.int8), ((1, 1, 32, 64), jnp.int8),
             ((32,), jnp.float32), ((64,), jnp.float32),
             ((32,), jnp.float32), ((64,), jnp.float32))


# (H, C, stride): mobilenetv2-cifar expanded depthwise layers
DEPTHWISE = {'384ch_s2': (8, 384, 2), '144ch_s1': (16, 144, 1)}


@pytest.mark.parametrize('case', sorted(DEPTHWISE))
def test_depthwise_conv_compiles_for_v5e(case, one_chip, no_compile_cache):
    h, c, stride = DEPTHWISE[case]
    fn = functools.partial(depthwise_conv, stride=stride, relu=True,
                           out_scale=0.05)
    _compile(fn, one_chip, ((B, h, h, c), jnp.int8),
             ((3, 3, 1, c), jnp.int8), ((), jnp.float32),
             ((c,), jnp.float32), ((c,), jnp.float32))


def test_fake_quant_fused_compiles_for_v5e(one_chip, no_compile_cache):
    """The QAT hot op on the 512 x 10 head weight."""
    _compile(fake_quant_fused, one_chip, ((512, 10), jnp.float32))

