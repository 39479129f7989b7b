"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def requantize(y, out_scale, qmax=127.0):
    """Static requantize: fp32 -> int8 on the ``out_scale`` grid.

    The jnp realization of the kernels' requantize epilogue — same op order
    (divide, round, clip, cast), so ref and Pallas paths agree bit-for-bit
    given bit-equal fp32 inputs.
    """
    return jnp.clip(jnp.round(y / out_scale), -qmax - 1.0,
                    qmax).astype(jnp.int8)


def quant_matmul_ref(x_q, w_q, sx, sw, out_dtype=jnp.float32):
    """int8 x (M,K) @ int8 w (K,N); sx per-tensor scalar or per-row (M,),
    per-col sw (N,).  Same epilogue op order as the kernel:
    ``acc * (sx * sw)``."""
    acc = jnp.einsum('mk,kn->mn', x_q.astype(jnp.int32), w_q.astype(jnp.int32),
                     preferred_element_type=jnp.int32)
    sx = jnp.asarray(sx, jnp.float32)
    scale = (sx[:, None] if sx.ndim else sx) * sw.astype(jnp.float32)[None, :]
    return (acc.astype(jnp.float32) * scale).astype(out_dtype)


def fake_quant_ref(w, bits: int):
    """Per-output-channel (last dim) symmetric fake quantization."""
    qmax = 2.0 ** (bits - 1) - 1.0
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(w / scale), -qmax - 1, qmax) * scale


def _int_conv(x_q, w_q, stride, groups):
    """SAME conv of integer codes, returned as fp32.

    fp32 holds every partial sum exactly while ``K * 128 * 128 < 2**24``
    (K = KH*KW*per-group CIN, i.e. K < 1024), so small-K convs run XLA's
    fast fp32 conv on the raw codes.  Larger K accumulates int8 codes in
    int32, exact at any K.  Either way int8 codes give the kernels' int32
    accumulator exactly.  Float codes (the QAT forward, whose codes carry
    gradients) always take the fp32 conv, so at K >= 1024 their partial
    sums round like any fp32 conv and match the kernels only approximately.
    """
    kh, kw, cg, _ = w_q.shape
    dt = (jnp.float32 if kh * kw * cg * 128 * 128 < 2 ** 24
          or x_q.dtype != jnp.int8 else jnp.int8)
    acc = jax.lax.conv_general_dilated(
        x_q.astype(dt), w_q.astype(dt), (stride, stride), 'SAME',
        feature_group_count=groups,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
        preferred_element_type=(jnp.float32 if dt == jnp.float32
                                else jnp.int32))
    return acc.astype(jnp.float32)


def quant_conv_ref(x_q, w_q, sx, sw, bias=None, *, stride=1, relu=False,
                   groups=1, out_dtype=jnp.float32, out_scale=None,
                   out_qmax=127.0):
    """lax.conv oracle for kernels/quant_conv.quant_conv (and, with
    ``groups == CIN``, kernels/depthwise_conv.depthwise_conv).

    Accumulates on the *raw integer codes* (:func:`_int_conv`, exact) and
    then applies the kernels' fp32 epilogue in their op order —
    ``acc * (sx * sw)``, bias, ReLU, optional requantize on the static
    ``out_scale`` grid.  Dequantizing the operands before the conv is the
    same math on paper but rounds differently, and a dynamic per-tensor
    requantize downstream turns that last-ulp difference into flipped int8
    codes; this order keeps the jnp serving path on the kernels' values.
    x_q int8 NHWC, w_q int8 HWIO, sx scalar, sw (COUT,).
    """
    acc = _int_conv(x_q, w_q, stride, groups)
    scale = jnp.asarray(sx, jnp.float32) * sw.astype(jnp.float32)
    y = acc * scale[None, None, None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, None, None, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    if out_scale is not None:
        return requantize(y, out_scale, out_qmax)
    return y.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=('stride', 'relu', 'out_dtype',
                                             'out_scale', 'out_qmax'))
def depthwise_conv_ref(x_q, w_q, sx, sw, bias=None, *, stride=1, relu=False,
                       out_dtype=jnp.float32, out_scale=None, out_qmax=127.0):
    """Bit-exact oracle for kernels/depthwise_conv.depthwise_conv:
    :func:`quant_conv_ref` with ``feature_group_count = CIN``.  x_q int8
    (B,H,W,CIN); w_q int8 (KH,KW,1,COUT) with COUT a multiple of CIN.

    Jitted on purpose: op-by-op dispatch compiles ``acc * scale + bias``
    without the fused multiply-add contraction XLA applies inside a traced
    program, which perturbs the fp32 result by ~1 ulp vs the (also
    compiled) Pallas kernel.  With both sides compiled the contraction is
    identical and the fp32 outputs agree bit-for-bit (the int8
    ``out_scale`` outputs agree either way — rounding absorbs the ulp).
    """
    return quant_conv_ref(x_q, w_q, sx, sw, bias, stride=stride, relu=relu,
                          groups=x_q.shape[-1], out_dtype=out_dtype,
                          out_scale=out_scale, out_qmax=out_qmax)


def lowrank_conv_ref(x_q, u_q, v_q, su, sv, bu, bv, *, sx, h_scale, stride=1,
                     relu=False, out_scale=None, h_qmax=127.0,
                     out_qmax=127.0):
    """Chained two-conv oracle for kernels/lowrank_conv.lowrank_conv: the
    u conv requantizes its output to int8 on the static ``h_scale`` grid
    (exactly what the fused kernel does to its VMEM intermediate), then the
    1x1 v conv applies the ordinary dequant(+bias)(+ReLU)(+requantize)
    epilogue."""
    v_q = v_q.reshape(1, 1, v_q.shape[-2], v_q.shape[-1])
    h_q = quant_conv_ref(x_q, u_q, sx, su, bu, stride=stride,
                         out_scale=h_scale, out_qmax=h_qmax)
    return quant_conv_ref(h_q, v_q, h_scale, sv, bv, relu=relu,
                          out_scale=out_scale, out_qmax=out_qmax)


def decode_attention_ref(q, k, v, valid):
    """q: (B,H,D); k,v: (B,S,K,D); valid: (B,S) bool. GQA decode oracle."""
    B, H, D = q.shape
    K = k.shape[2]
    g = H // K
    qg = q.reshape(B, K, g, D) * (D ** -0.5)
    logits = jnp.einsum('bkgd,bskd->bkgs', qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum('bkgs,bskd->bkgd', p, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
