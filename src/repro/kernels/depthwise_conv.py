"""Direct (non-im2col) Pallas depthwise/grouped conv kernel — the serving
realization of the Q pass for MobileNet's depthwise layers.

A depthwise conv is block-diagonal in im2col form: routing it through the
int8 matmul tiles would waste ~CIN x of every MXU tile, which is why the
serving path previously *fell back* to a dequantized ``lax.conv`` for
grouped convs — leaving ~21% of MobileNet's MACs in fp32
(``ServingModel.summary()`` ``fallback_mac_fraction``).  This kernel kills
that fallback with the operation's natural lowering: per-channel int8
multiply-accumulates over the KH x KW spatial window on the VPU (channels
on the 128 lane axis, no patch materialization, no MXU), with the shared
requantize epilogue — int32 accumulator -> static scale -> int8 out — so
depthwise layers are int8-in / int8-out in HBM like every other layer.

Lowering: the input is SAME-padded outside the kernel (symmetric
quantization has zero-point 0, so the int8 zero padding is value-exact) and
channels are padded to the 128 lane.  Grouped convs with per-group input
depth 1 — i.e. ``groups == CIN`` with any channel multiplier — are served
by expanding the input channel axis to the output channels
(``x_e[..., o] = x[..., o // mult]``, a pure int8 memory-layout op);
per-group depth > 1 has no per-channel lowering and stays on the declared
fallback (no such layer exists in this repo's families).  Grid is
``(B, COUT/bc)``: each step holds one padded spatial plane in VMEM —
de-interleaved into its stride phases outside the kernel
(:func:`_phase_planes`), so every tap is a unit-stride slice — unrolls the
KH*KW taps as multiply-accumulates into an int32 register tile, and runs
the epilogue once — one kernel launch per layer, zero accumulator traffic
to HBM.

Bit-exactness contract (tested): the int32 accumulation is exact, and the
fp32 epilogue op order (``acc * (sx * sw) + b``, ReLU, requantize) matches
``ref.depthwise_conv_ref`` — which accumulates exactly via ``lax.conv`` on
the raw integer codes — so kernel and oracle agree bit-for-bit, not just
allclose (depthwise sums of <= KH*KW*127^2 stay far below 2^24).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, VMEM_BUDGET, pad_to


def fits_depthwise(w_shape) -> bool:
    """Can this grouped conv serve on the depthwise kernel?

    True for per-group input depth 1 (HWIO weight ``(KH, KW, 1, COUT)``,
    the ``groups == CIN`` family — plain depthwise and channel-multiplier
    variants).  Generic grouped convs (per-group depth > 1) keep the
    declared fallback; none exist in this repo's model families.
    """
    return len(w_shape) == 4 and w_shape[2] == 1


def _same_pads(h: int, w: int, kh: int, kw: int, stride: int):
    """SAME-padding geometry (identical to quant_conv's im2col plan and
    lax.conv 'SAME'): returns ((top, bottom), (left, right), oh, ow)."""
    oh, ow = -(-h // stride), -(-w // stride)
    pad_h = max((oh - 1) * stride + kh - h, 0)
    pad_w = max((ow - 1) * stride + kw - w, 0)
    return ((pad_h // 2, pad_h - pad_h // 2),
            (pad_w // 2, pad_w - pad_w // 2), oh, ow)


def _phase_planes(x, stride: int):
    """De-interleave a padded (B, HP, WP, C) plane into its stride phases:
    (B, stride**2, HP/stride, WP/stride, C), phase ``a * stride + b``
    holding rows ``a::stride`` and columns ``b::stride`` (HP/WP zero-padded
    up to a stride multiple first — value-exact, symmetric int8 zeros).

    Tap (i, j) of a stride-s conv then reads phase ``(i % s, j % s)`` at
    the unit-stride offset ``(i // s, j // s)``, so the kernel never takes
    a strided slice in VMEM (Mosaic refuses strides other than 1 there).
    A pure int8 memory-layout op; stride 1 is a free reshape."""
    B, hp, wp, C = x.shape
    if stride == 1:
        return x[:, None]
    hq, wq = -(-hp // stride), -(-wp // stride)
    x = jnp.pad(x, ((0, 0), (0, hq * stride - hp), (0, wq * stride - wp),
                    (0, 0)))
    x = x.reshape(B, hq, stride, wq, stride, C).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(B, stride * stride, hq, wq, C)


def _dw_kernel(x_ref, w_ref, sx_ref, sw_ref, b_ref, o_ref, *, kh, kw,
               stride, oh, ow, relu, out_scale, out_qmax):
    planes = [x_ref[0, p] for p in range(stride * stride)]  # (HQ, WQ, bc)
    w = w_ref[...].astype(jnp.int32)                 # (KH*KW, bc)
    acc = jnp.zeros(o_ref.shape[1:], jnp.int32)      # (OH, OW, bc) registers
    for i in range(kh):                              # unrolled taps: the
        for j in range(kw):                          # whole window sum is
            x = planes[(i % stride) * stride + j % stride]  # per-channel
            r0, c0 = i // stride, j // stride        # VPU FMAs, unit stride
            win = jax.lax.slice(x, (r0, c0, 0), (r0 + oh, c0 + ow,
                                                 x.shape[-1]))
            t = i * kw + j
            acc += win.astype(jnp.int32) * w[t:t + 1][None]
    # shared epilogue, same fp32 op order as quant_matmul's: dequant on the
    # (sx * sw) product, bias, ReLU, optional static requantize to int8
    y = acc.astype(jnp.float32) * (sx_ref[0, 0] * sw_ref[...])[None]
    y = y + b_ref[...][None]
    if relu:
        y = jnp.maximum(y, 0.0)
    if out_scale is not None:
        y = jnp.clip(jnp.round(y / out_scale), -out_qmax - 1.0, out_qmax)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    'stride', 'relu', 'bc', 'out_dtype', 'interpret', 'out_scale',
    'out_qmax'))
def depthwise_conv(x_q, w_q, sx, sw, bias=None, *, stride=1, relu=False,
                   bc=LANE, out_dtype=jnp.float32, interpret=False,
                   out_scale=None, out_qmax=127.0):
    """Int8 NHWC depthwise/grouped conv, direct (non-im2col) Pallas lowering.

    x_q: int8 (B,H,W,CIN); w_q: int8 (KH,KW,1,COUT) with COUT an integer
    multiple of CIN (the channel multiplier; COUT == CIN is plain
    depthwise); sx: scalar fp32 per-tensor activation scale (static float
    or traced scalar — it rides in SMEM as a (1, 1) operand, not a trace
    constant); sw: (COUT,) fp32 static per-channel weight scales; bias:
    (COUT,) fp32 or None.  Returns (B,OH,OW,COUT) ``out_dtype``, or int8
    when the ``out_scale`` requantize epilogue is selected (cf.
    quant_matmul).
    """
    B, H, W, C = x_q.shape
    kh, kw, cg, n = w_q.shape
    assert cg == 1, f'per-group input depth must be 1, got {cg}'
    assert n % C == 0, (n, C)
    mult = n // C
    if mult > 1:        # channel multiplier: output channel o reads o//mult
        x_q = jnp.repeat(x_q, mult, axis=-1)
    (ph, pw, oh, ow) = _same_pads(H, W, kh, kw, stride)
    x_q = jnp.pad(x_q, ((0, 0), ph, pw, (0, 0)))
    np_ = pad_to(n)
    bc = min(bc, np_)
    if np_ != n:
        x_q = jnp.pad(x_q, ((0, 0), (0, 0), (0, 0), (0, np_ - n)))
    x_q = _phase_planes(x_q, stride)
    _, n_ph, hq, wq, _ = x_q.shape
    assert (n_ph * hq * wq + 4 * oh * ow + 4 * oh * ow) * bc <= \
        VMEM_BUDGET, (n_ph, hq, wq, bc)
    w2 = jnp.pad(w_q.reshape(kh * kw, n), ((0, 0), (0, np_ - n)))

    def row(a):       # (1, np_) rows: no 1-D f32 blocks under Mosaic
        return jnp.pad(a.astype(jnp.float32).reshape(1, n),
                       ((0, 0), (0, np_ - n)))
    sw = row(sw)
    b = row(jnp.zeros((n,), jnp.float32) if bias is None else bias)
    if out_scale is not None:
        out_scale, out_dtype = float(out_scale), jnp.int8
    out = pl.pallas_call(
        functools.partial(_dw_kernel, kh=kh, kw=kw, stride=stride, oh=oh,
                          ow=ow, relu=relu, out_scale=out_scale,
                          out_qmax=float(out_qmax)),
        grid=(B, np_ // bc),
        in_specs=[
            pl.BlockSpec((1, n_ph, hq, wq, bc),
                         lambda b, c: (b, 0, 0, 0, c)),
            pl.BlockSpec((kh * kw, bc), lambda b, c: (0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bc), lambda b, c: (0, c)),
            pl.BlockSpec((1, bc), lambda b, c: (0, c)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, bc), lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, oh, ow, np_), out_dtype),
        interpret=interpret,
    )(x_q, w2, jnp.reshape(jnp.asarray(sx, jnp.float32), (1, 1)), sw, b)
    return out[..., :n]
