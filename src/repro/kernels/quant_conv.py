"""Int8 NHWC conv lowered to MXU matmul tiles — the serving realization of
the paper's Q pass for conv layers.

Lowering: SAME-padded im2col turns the conv into
``patches (B*OH*OW, KH*KW*CIN) @ w (KH*KW*CIN, COUT)`` — the patch axis
becomes the matmul K axis, accumulated tile-by-tile in the int32 VMEM
scratch of the shared quant_matmul kernel (kernels/quant_matmul.py), with
the dequant + bias + ReLU (or requantize — see ``out_scale``) epilogue
fused into the final K step.  Patch extraction itself is a pure
memory-layout op: one int8 gather over the padded spatial plane, with the
index computation cached per geometry (``_im2col_plan``) so it never
re-runs across calls or traces; all the FLOPs run on the Pallas kernel.

Because quantization is symmetric (zero-point 0), the SAME zero-padding is
value-exact in the quantized domain: padded int8 zeros contribute nothing
to the int32 accumulator.

Grouped convs (MobileNet depthwise — ~21% of its MACs, nothing like
negligible) are block-diagonal in im2col form: int8 matmul tiles would be
~CIN x wasted, so the ops-layer wrapper (kernels/ops.py) serves them on
the direct per-channel kernel in kernels/depthwise_conv.py instead of
this one — int8 VPU MACs, no patch materialization, no fp32 fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.quant_matmul import quant_matmul


def conv_out_hw(h: int, w: int, stride: int) -> tuple[int, int]:
    """SAME-padding output spatial dims."""
    return -(-h // stride), -(-w // stride)


@functools.lru_cache(maxsize=None)
def _im2col_plan(h: int, w: int, kh: int, kw: int, stride: int):
    """Cached im2col geometry: SAME pads plus the flat gather indices.

    Returns (pads, (oh, ow), idx) where ``idx`` is an int32 numpy array of
    shape (OH*OW*KH*KW,) indexing the *padded* HP*WP spatial plane in
    (oh, ow)-major, (kh, kw)-minor order.  The index computation is pure
    Python/numpy on static shapes — the lru_cache means it runs once per
    layer geometry for the life of the process, not once per call/trace
    (the old shift+concat built kh*kw strided slices at every trace).
    """
    oh, ow = conv_out_hw(h, w, stride)
    pad_h = max((oh - 1) * stride + kh - h, 0)
    pad_w = max((ow - 1) * stride + kw - w, 0)
    hp, wp = h + pad_h, w + pad_w
    rows = (np.arange(oh)[:, None] * stride + np.arange(kh)[None, :])
    cols = (np.arange(ow)[:, None] * stride + np.arange(kw)[None, :])
    # (oh, ow, kh, kw) -> flat index into the padded plane
    idx = (rows[:, None, :, None] * wp + cols[None, :, None, :])
    return ((pad_h // 2, pad_h - pad_h // 2),
            (pad_w // 2, pad_w - pad_w // 2)), (oh, ow), \
        idx.reshape(-1).astype(np.int32)


def im2col_nhwc(x, kh: int, kw: int, stride: int = 1):
    """SAME im2col: x (B,H,W,C) -> patches (B*OH*OW, KH*KW*C), plus (OH,OW).

    The flattened patch axis is (kh, kw, C)-major — exactly the order of
    ``w.reshape(KH*KW*C, COUT)`` for HWIO weights.  Works on any dtype; the
    int8 serving path feeds already-quantized activations so the zero pad
    is exact.  Lowered as one gather over the padded spatial plane with
    cached (per-geometry) indices — a pure memory-layout op, under
    ``jax.named_scope('im2col')`` so a device profile tells it from the
    matmul.
    """
    B, H, W, C = x.shape
    (ph, pw), (oh, ow), idx = _im2col_plan(H, W, kh, kw, stride)
    with jax.named_scope('im2col'):
        x = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
        flat = x.reshape(B, x.shape[1] * x.shape[2], C)
        patches = jnp.take(flat, jnp.asarray(idx), axis=1)
        return patches.reshape(B * oh * ow, kh * kw * C), (oh, ow)


@functools.partial(jax.jit, static_argnames=('stride', 'relu', 'bm', 'bn',
                                             'bk', 'out_dtype', 'interpret',
                                             'out_scale', 'out_qmax'))
def quant_conv(x_q, w_q, sx, sw, bias=None, *, stride=1, relu=False,
               bm=128, bn=128, bk=256, out_dtype=jnp.float32,
               interpret=False, out_scale=None, out_qmax=127.0):
    """Int8 NHWC conv with fused dequant + bias + ReLU epilogue.

    x_q: int8 (B,H,W,CIN); w_q: int8 (KH,KW,CIN,COUT); sx: scalar fp32
    per-tensor activation scale; sw: (COUT,) fp32 static per-channel weight
    scales; bias: (COUT,) fp32 or None.  Returns (B,OH,OW,COUT) out_dtype.

    ``out_scale`` (static float) selects the requantize epilogue of
    kernels/quant_matmul.py: the output is int8 at that scale, so the
    activation never round-trips through fp32 HBM between layers.
    """
    B, H, W, C = x_q.shape
    kh, kw, c2, n = w_q.shape
    assert C == c2, (C, c2)
    patches, (oh, ow) = im2col_nhwc(x_q, kh, kw, stride)
    out = quant_matmul(patches, w_q.reshape(kh * kw * C, n),
                       jnp.asarray(sx, jnp.float32),
                       sw.astype(jnp.float32), bias,
                       bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, relu=relu,
                       interpret=interpret, out_scale=out_scale,
                       out_qmax=out_qmax)
    return out.reshape(B, oh, ow, n)
