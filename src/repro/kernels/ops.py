"""Public jit'd wrappers over the Pallas kernels.

On CPU (this container) kernels run in ``interpret=True`` mode, which
executes the kernel body in Python for correctness validation; on TPU the
same BlockSpecs compile to Mosaic.  ``use_pallas=False`` falls back to the
pure-jnp oracle (used by models at training time on CPU, where interpret
mode is too slow to train through).

Serving entry points (consumed by core/export.py):

* :func:`prequantize_weight` — per-out-channel weight int8 quantization,
  run ONCE at export; the returned (w_q, sw) are static at serve time.
* :func:`quant_dense` / :func:`quant_conv_nhwc` — dynamic activation
  quantization + the int8 Pallas matmul/conv kernels with fused epilogue
  (the PR-1 exported path: one abs-max pass per layer, fp32 between
  layers).
* :func:`quant_conv_static` / :func:`quant_dense_static` /
  :func:`depthwise_conv_static` / :func:`lowrank_conv_nhwc` — the
  int8-resident path: activations arrive already int8 on a *static* scale
  captured at export calibration, and the requantize epilogue
  (``out_scale``) keeps them int8 on the way out.
  ``depthwise_conv_static`` serves grouped/depthwise convs on the direct
  per-channel kernel (kernels/depthwise_conv.py) — there is no fp32
  fallback left on the resident path.  ``lowrank_conv_nhwc`` serves a
  factored (u, v) conv pair as ONE Pallas launch
  (kernels/lowrank_conv.py); its jnp fallback chains the two convs with
  identical requantize math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _pallas_decode
from repro.kernels.depthwise_conv import depthwise_conv as _pallas_dw_conv
from repro.kernels.depthwise_conv import fits_depthwise
from repro.kernels.fake_quant import fake_quant as _pallas_fake_quant
from repro.kernels.fake_quant import fake_quant_fused as _pallas_fq_fused
from repro.kernels.lowrank_conv import lowrank_conv as _pallas_lr_conv
from repro.kernels.quant_conv import quant_conv as _pallas_qconv
from repro.kernels.quant_matmul import quant_matmul as _pallas_qmm


def _interpret() -> bool:
    return jax.default_backend() == 'cpu'


def quant_matmul(x_q, w_q, sx, sw, bias=None, *, use_pallas=True, relu=False,
                 **kw):
    if not use_pallas:
        y = ref.quant_matmul_ref(x_q, w_q, sx, sw)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return jnp.maximum(y, 0.0) if relu else y
    return _pallas_qmm(x_q, w_q, sx, sw, bias, relu=relu,
                       interpret=_interpret(), **kw)


def fake_quant(w, bits=8, *, use_pallas=True, fused=None, **kw):
    """Fake-quantize w; ``fused`` selects the single-HBM-pass kernel.

    ``fused=None`` (auto) picks it whenever the (K, bn) column stripe fits
    a conservative VMEM budget — true for every weight in this repo — and
    falls back to the two-kernel amax→quantize path for huge K.
    """
    if not use_pallas:
        return ref.fake_quant_ref(w, bits)
    if fused is None:
        from repro.kernels.tiling import VMEM_BUDGET
        bn = kw.get('bn', 256)
        fused = w.shape[0] * min(bn, w.shape[1]) * 4 <= VMEM_BUDGET // 2
    if fused:
        kw.pop('bk', None)
        return _pallas_fq_fused(w, bits=bits, interpret=_interpret(), **kw)
    return _pallas_fake_quant(w, bits=bits, interpret=_interpret(), **kw)


def decode_attention(q, k, v, valid, *, use_pallas=True, **kw):
    if not use_pallas:
        return ref.decode_attention_ref(q, k, v,
                                        jnp.broadcast_to(valid,
                                                         (q.shape[0],
                                                          k.shape[1])))
    return _pallas_decode(q, k, v, valid, interpret=_interpret(), **kw)


# --------------------------------------------------------- int8 serving path


def _act_qmax(a_bits: int) -> float:
    return 2.0 ** (a_bits - 1) - 1.0


def prequantize_weight(w, *, bits: int = 8):
    """Per-out-channel (last dim) symmetric int8 weight quantization.

    Run once at export time — the serving kernels consume (w_q, sw) as
    static operands and never recompute the weight abs-max.  Works on any
    rank: the reduction covers every axis but the last.  Routes through
    core.quantization.quantize_weight (the single weight quantizer, incl.
    the bits=1 DoReFa branch).  Returns (w_q int8, sw (out,) fp32).
    """
    from repro.core.quantization import quantize_weight
    w_q, scale = quantize_weight(w.astype(jnp.float32), bits, axis=-1)
    return w_q.astype(jnp.int8), scale.reshape(-1).astype(jnp.float32)


def quantize_act(x, *, a_bits: int = 8, per_row: bool = False):
    """Dynamic activation quantization (the only per-call scale compute).

    per_row=True gives each row of a 2D x its own scale; otherwise one
    per-tensor scale (matching core.quantization.fake_quant_act's QAT
    clip, so serving stays on the QAT grid).  Returns (x_q int8, sx).
    """
    qmax = _act_qmax(a_bits)
    if per_row:
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=1), 1e-8) * (1.0 / qmax)
        xq = jnp.clip(jnp.round(x / s[:, None]), -qmax - 1, qmax)
    else:
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) * (1.0 / qmax)
        xq = jnp.clip(jnp.round(x / s), -qmax - 1, qmax)
    return xq.astype(jnp.int8), s.astype(jnp.float32)


def quant_dense(x, w_q, sw, *, a_bits=8, per_row=True, use_pallas=True, **kw):
    """Int8 dense with prequantized weights: x fp32 (M,K) @ w_q int8 (K,N).

    Activations are dynamically quantized (per-row or per-tensor scale);
    weight scales sw (N,) are static.  Returns fp32 (M, N).
    """
    xq, sx = quantize_act(x, a_bits=a_bits, per_row=per_row)
    return quant_matmul(xq, w_q, sx, sw.reshape(-1), use_pallas=use_pallas,
                        **kw)


def quantize_dense_int8(x, w, **kw):
    """Dynamic-quantize x and w to int8 and run the quantized matmul.

    Thin wrapper over prequantize_weight + quant_dense, kept for callers
    that hold fp32 weights; the serving path prequantizes once at export
    and calls quant_dense directly.
    """
    w_q, sw = prequantize_weight(w)
    return quant_dense(x, w_q, sw, **kw)


def quant_conv_nhwc(x, w_q, sw, bias=None, *, stride=1, groups=1, relu=False,
                    a_bits=8, use_pallas=True, **kw):
    """Int8 NHWC conv with prequantized weights and fused epilogue.

    x fp32 (B,H,W,CIN); w_q int8 (KH,KW,CIN,COUT); sw (COUT,) static.
    Activations get one dynamic per-tensor scale (the QAT grid).  Grouped
    convs with per-group depth 1 (depthwise, any channel multiplier) serve
    on the direct per-channel kernel (kernels/depthwise_conv.py) — im2col
    would waste ~CIN x of MXU tiles on their block-diagonal structure.
    Only per-group depth > 1 (absent from this repo's families) still
    dequantizes through lax.conv.
    """
    xq, sx = quantize_act(x, a_bits=a_bits)
    if groups > 1:
        if use_pallas and fits_depthwise(w_q.shape):
            return _pallas_dw_conv(xq, w_q, sx, sw, bias, stride=stride,
                                   relu=relu, interpret=_interpret())
        return ref.quant_conv_ref(xq, w_q, sx, sw, bias, stride=stride,
                                  relu=relu, groups=groups)
    if not use_pallas:
        return ref.quant_conv_ref(xq, w_q, sx, sw, bias, stride=stride,
                                  relu=relu)
    return _pallas_qconv(xq, w_q, sx, sw, bias, stride=stride, relu=relu,
                         interpret=_interpret(), **kw)


# ------------------------------------------- int8-resident serving entries


def quant_conv_static(x_q, w_q, sw, bias=None, *, sx, stride=1, relu=False,
                      out_scale=None, out_qmax=127.0, use_pallas=True, **kw):
    """Int8 conv on an *already-quantized* activation with a static scale.

    x_q int8 (B,H,W,CIN) on the static per-tensor grid ``sx`` (a Python
    float from export calibration); no abs-max pass runs.  With
    ``out_scale`` the output is int8 on that static grid — the layer is
    int8-in/int8-out in HBM.
    """
    if not use_pallas:
        return ref.quant_conv_ref(x_q, w_q, sx, sw, bias, stride=stride,
                                  relu=relu, out_scale=out_scale,
                                  out_qmax=out_qmax)
    return _pallas_qconv(x_q, w_q, sx, sw, bias, stride=stride, relu=relu,
                         out_scale=out_scale, out_qmax=out_qmax,
                         interpret=_interpret(), **kw)


def depthwise_conv_static(x_q, w_q, sw, bias=None, *, sx, stride=1,
                          relu=False, out_scale=None, out_qmax=127.0,
                          use_pallas=True, **kw):
    """Int8 depthwise/grouped conv on a statically-quantized activation.

    The resident-path twin of :func:`quant_conv_static` for grouped convs
    with per-group input depth 1: x_q int8 (B,H,W,CIN) on the static grid
    ``sx``; w_q int8 (KH,KW,1,COUT) with COUT a multiple of CIN.  Serves on
    the direct per-channel Pallas kernel — int8 MACs, shared requantize
    epilogue, bit-exact vs ref.depthwise_conv_ref — so MobileNet's
    depthwise layers are int8-in/int8-out like every other resident layer
    (the old fp32 lax.conv fallback is gone).
    """
    if not use_pallas:
        return ref.depthwise_conv_ref(x_q, w_q, sx, sw, bias, stride=stride,
                                      relu=relu, out_scale=out_scale,
                                      out_qmax=out_qmax)
    return _pallas_dw_conv(x_q, w_q, sx, sw, bias, stride=stride, relu=relu,
                           out_scale=out_scale, out_qmax=out_qmax,
                           interpret=_interpret(), **kw)


def quant_dense_static(x_q, w_q, sw, bias=None, *, sx, relu=False,
                       out_scale=None, out_qmax=127.0, use_pallas=True, **kw):
    """Int8 dense on a statically-quantized activation (cf.
    :func:`quant_conv_static`).  x_q int8 (M,K); returns fp32 (M,N), or
    int8 when ``out_scale`` is set."""
    if not use_pallas:
        y = ref.quant_matmul_ref(x_q, w_q, sx, sw.reshape(-1))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        if out_scale is not None:
            return ref.requantize(y, out_scale, out_qmax)
        return y
    return _pallas_qmm(x_q, w_q, sx, sw.reshape(-1), bias, relu=relu,
                       out_scale=out_scale, out_qmax=out_qmax,
                       interpret=_interpret(), **kw)


def lowrank_conv_nhwc(x_q, u_q, v_q, su, sv, bu, bv, *, sx, h_scale,
                      stride=1, relu=False, out_scale=None, h_qmax=127.0,
                      out_qmax=127.0, use_pallas=True, **kw):
    """Serve a factored (u, v) conv pair — ONE Pallas launch on the kernel
    path (kernels/lowrank_conv.py: the rank intermediate never leaves
    VMEM), or the chained jnp reference with identical requantize math."""
    if not use_pallas:
        return ref.lowrank_conv_ref(x_q, u_q, v_q, su, sv, bu, bv, sx=sx,
                                    h_scale=h_scale, stride=stride,
                                    relu=relu, out_scale=out_scale,
                                    h_qmax=h_qmax, out_qmax=out_qmax)
    return _pallas_lr_conv(x_q, u_q, v_q, su, sv, bu, bv, sx=float(sx),
                           h_scale=float(h_scale), stride=stride, relu=relu,
                           out_scale=out_scale, h_qmax=h_qmax,
                           out_qmax=out_qmax, interpret=_interpret(), **kw)
