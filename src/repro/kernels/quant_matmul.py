"""W8A8 quantized matmul Pallas kernel — the TPU realization of the paper's
Q pass at inference time.

The GPU papers realize low-bit wins with bit-serial/CUDA-core tricks; on TPU
the win comes from feeding the 128x128 MXU int8 operands (2x MACs/cycle vs
bf16 on v5e) and halving HBM traffic.  Tiling: (bm x bk) @ (bk x bn) blocks
resident in VMEM, fp32 dequant fused into the epilogue with a per-tensor
(SMEM scalar) or per-row activation scale and per-column weight scales.

Grid is (M/bm, N/bn, K/bk) with the K axis innermost: the int32 accumulator
lives in a VMEM scratch and is rescaled+flushed once per (m, n) tile.  The
epilogue can optionally fuse a per-column bias add and ReLU — this is what
the exported serving path (core/export.py) uses for conv layers, where the
matmul K axis is the im2col patch axis.

Awkward dims (primes, non-128 multiples with no decent divisor) are
zero-padded to the next 128 multiple and sliced back — zero int8 rows/cols
contribute nothing to the int32 accumulator, so padding is value-exact.

``out_scale`` turns the epilogue into a **requantize** epilogue: after the
fused dequant(+bias)(+ReLU) the result is divided by a *static* output
scale, rounded, clipped to ``out_qmax`` and written as int8 — the int8-
resident serving path (core/export.py) uses this so activations stay int8
in HBM between layers; the next kernel consumes them with the same static
scale, so no per-call abs-max pass ever touches the activation tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, fit_or_pad


def _qmm_kernel(*refs, n_k, relu, has_bias, row_scale, out_scale, out_qmax):
    x_ref, w_ref, sx_ref, sw_ref, *rest = refs
    b_ref = rest.pop(0) if has_bias else None
    o_ref, acc_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _done():
        # (bm, 1) per-row column from VMEM, or the per-tensor scalar from
        # SMEM; times the (1, bn) weight-scale row -> the dequant scale
        sx = sx_ref[...] if row_scale else sx_ref[0, 0]
        y = acc_ref[...].astype(jnp.float32) * (sx * sw_ref[...])
        if b_ref is not None:
            y = y + b_ref[...]
        if relu:
            y = jnp.maximum(y, 0.0)
        if out_scale is not None:   # requantize epilogue: int8 stays in HBM
            y = jnp.clip(jnp.round(y / out_scale), -out_qmax - 1.0, out_qmax)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('bm', 'bn', 'bk', 'out_dtype',
                                             'relu', 'interpret', 'out_scale',
                                             'out_qmax'))
def quant_matmul(x_q, w_q, sx, sw, bias=None, *, bm=128, bn=128, bk=256,
                 out_dtype=jnp.float32, relu=False, interpret=False,
                 out_scale=None, out_qmax=127.0):
    """x_q: int8 (M,K); w_q: int8 (K,N); sx: fp32 scalar (per-tensor) or
    (M,) (per-row); sw: (N,) fp32.

    Optional fused epilogue: ``bias`` (N,) fp32 added after dequant, then
    ReLU when ``relu=True``.  Returns (M, N) ``out_dtype``.

    ``out_scale`` (static Python float) switches the epilogue to requantize:
    the fp32 result is divided by it, rounded and clipped to ``out_qmax``,
    and the output is int8 (``out_dtype`` is ignored) — the next layer
    consumes it directly with the same static scale.

    Scale operands never travel as 1-D blocks (Mosaic cannot lay out a 1-D
    f32 block that is not the whole array): a per-tensor ``sx`` rides in
    SMEM as a (1, 1) scalar, a per-row ``sx`` as an (M, 1) column, and
    ``sw`` / ``bias`` as (1, N) rows.
    """
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2
    if out_scale is not None:
        out_scale, out_dtype = float(out_scale), jnp.int8
    (bm, Mp), (bn, Np), (bk, Kp) = (fit_or_pad(bm, M),
                                    fit_or_pad(bn, N, align=LANE),
                                    fit_or_pad(bk, K, align=LANE))
    sx = jnp.asarray(sx, jnp.float32)
    row_scale = sx.ndim == 1
    sx = sx.reshape(-1, 1) if row_scale else sx.reshape(1, 1)
    sw = sw.astype(jnp.float32).reshape(1, N)
    if bias is not None:
        bias = bias.astype(jnp.float32).reshape(1, N)
    if (Mp, Np, Kp) != (M, N, K):
        x_q = jnp.pad(x_q, ((0, Mp - M), (0, Kp - K)))
        w_q = jnp.pad(w_q, ((0, Kp - K), (0, Np - N)))
        if row_scale:
            sx = jnp.pad(sx, ((0, Mp - M), (0, 0)))
        sw = jnp.pad(sw, ((0, 0), (0, Np - N)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, Np - N)))
    n_k = Kp // bk
    grid = (Mp // bm, Np // bn, n_k)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        (pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)) if row_scale
         else pl.BlockSpec(memory_space=pltpu.SMEM)),
        pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
    ]
    args = [x_q, w_q, sx, sw]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, relu=relu,
                          has_bias=bias is not None, row_scale=row_scale,
                          out_scale=out_scale, out_qmax=float(out_qmax)),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(*args)
    return out[:M, :N] if (Mp, Np) != (M, N) else out
