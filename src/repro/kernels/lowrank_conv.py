"""Fused low-rank conv kernel — a factored (u, v) conv pair in ONE Pallas
launch (the serving realization of the chain's L∘Q composition).

The 'L' pass (core/lowrank.py) splits a conv (KH,KW,CIN,COUT) into a
spatial conv down to rank ``r`` ('u') chained with a 1x1 conv back up
('v').  Served naively that is two kernel launches with an
(B,OH,OW,r) int8 intermediate bouncing through HBM — and because the rank
bottleneck usually has r < 128, the second matmul wastes most of each
128-wide MXU tile on the K axis.  This kernel fuses the pair:

    patches (M, K1) @ u_q (K1, Rp)   -> int32 acc     (K1 grid axis)
    requantize(acc * sx*su + bu) / h_scale -> int8 h  (VMEM scratch only)
    h (bm, Rp) @ v_q (Rp, bn)        -> int32         (per COUT tile)
    dequant + bias (+ReLU) (+requantize)              (epilogue)

The r-dim intermediate lives entirely in VMEM scratch, zero-padded to the
128 lane when r < 128 — padded u columns are zero int8, so the padded
intermediate quantizes to exactly 0 and contributes nothing to the second
matmul (padding is value-exact, and the whole launch is **bit-exact** with
the chained quant_conv(u, out_scale=h_scale) → quant_conv(v) path: the
int32 accumulation domains and the fp32 epilogue op order are identical).

Grid is (M/bm, K1/bk, N/bn) with the COUT axis innermost: the u-stage
operands (patches block, u block) are indexed by (i, k) only, so they are
fetched once per K step and never re-streamed while the N axis cycles; the
int8 ``h`` scratch persists across N tiles, so the v stage is one
(bm, Rp) x (Rp, bn) dot per COUT tile with zero recompute.  That removes
the old whole-width (Rp, Np) v block and its VMEM assert — any COUT now
fits (``fits_fused`` keeps only the rank envelope).  The one cost of this
grid order: the (bm, bn) output block is revisited (and flushed) once per
K step but only written on the last, so fused output traffic is n_k x the
chained path's — ``lowering_costs`` below charges exactly that, and the
layer-plan compiler (core/export.py) picks fused vs chained per layer from
it instead of assuming fused always wins.

All activation scales here are **static** Python floats captured at export
calibration — no abs-max pass ever reads the activation tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_conv import im2col_nhwc
from repro.kernels.tiling import (LANE, VMEM_BUDGET, device_peaks,
                                  fit_or_pad, pad_to)

# Per-launch dispatch overhead lowering_costs charges (the term the
# two-launch chained path pays twice) — an assumption, not a measurement.
LAUNCH_US = 2.0


def fits_fused(r: int, cout: int, *, bm: int = 128) -> bool:
    """Can a factored (u, v) pair with this rank/width serve as ONE launch?

    True when the lane-padded rank fits a single 128-wide K tile for the v
    matmul — the bit-exactness envelope (one int32 dot over the whole rank,
    the same accumulation domain as the chained path's single K tile).
    COUT no longer matters: the N axis is a grid dimension, so any width
    streams through (bm, bn) tiles against the persistent h scratch.  The
    layer-plan compiler (core/export.py) chains the two kernels when this
    is False — and even when it is True, picks fused vs chained by
    :func:`lowering_costs`, not by fiat.
    """
    del cout, bm   # kept for API compat: width/M-tile no longer constrain
    return pad_to(r) <= 128


def lowering_costs(m: int, k1: int, r: int, n: int, *, bm: int = 128,
                   bk: int = 256, bn: int = 128) -> dict:
    """Analytic cost (us) of serving one factored conv fused vs chained.

    Models the exact block geometry both lowerings run (same fit_or_pad /
    pad_to tiling as the kernels): MAC count is identical, so the decision
    is traffic + launches.  Fused pays n_k spurious output flushes (the
    (bm, bn) block is revisited per K step, written only on the last) but
    streams the u-stage operands once and never round-trips h through HBM;
    chained pays a second launch and the (M, Rp) h write+read but flushes
    each output block exactly once.  Per-launch time is the roofline max of
    its compute and traffic terms; the chained total is the sum of its two
    launches.  Used by core/export.py ``select_kernels='model'`` (the
    default) — 'measure' mode times the two lowerings instead.  Peaks come
    from :func:`tiling.device_peaks`: the chip's own row on a TPU, the v5e
    row off the chip (``peaks_modelled=True`` in the result).
    """
    (bm, mp), (bk, k1p) = fit_or_pad(bm, m), fit_or_pad(bk, k1, align=LANE)
    (bn, np_) = fit_or_pad(bn, n, align=LANE)
    rp = pad_to(r)
    n_m, n_k = mp // bm, k1p // bk
    peaks = device_peaks()
    macs_per_us = peaks['int8_ops'] / 2 / 1e6      # 2 ops per MAC
    bytes_per_us = peaks['hbm_bytes_per_s'] / 1e6
    macs_u = mp * k1p * rp          # padded-domain MACs, what the MXU runs
    macs_v = mp * rp * np_
    fused_bytes = (mp * k1p              # patches: once per (i, k), N inner
                   + n_m * k1p * rp     # u re-streamed per M tile
                   + n_m * rp * np_     # v re-streamed per M tile
                   + n_k * mp * np_)    # output flushed once per K revisit
    chained_bytes_u = mp * k1p + n_m * k1p * rp + mp * rp
    chained_bytes_v = mp * rp + n_m * rp * np_ + mp * np_
    fused_us = LAUNCH_US + max((macs_u + macs_v) / macs_per_us,
                               fused_bytes / bytes_per_us)
    chained_us = (2 * LAUNCH_US
                  + max(macs_u / macs_per_us, chained_bytes_u / bytes_per_us)
                  + max(macs_v / macs_per_us, chained_bytes_v / bytes_per_us))
    return {'fused_us': fused_us, 'chained_us': chained_us,
            'fused_bytes': fused_bytes,
            'chained_bytes': chained_bytes_u + chained_bytes_v,
            'macs': macs_u + macs_v, 'peaks_kind': peaks['kind'],
            'peaks_modelled': peaks['modelled']}


def _lr_kernel(x_ref, u_ref, su_ref, bu_ref, v_ref, sv_ref, bv_ref, o_ref,
               acc_ref, hq_ref, *, n_k, sx, h_scale, h_qmax, relu, out_scale,
               out_qmax):
    k = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when((k == 0) & (n == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n == 0)   # u-stage accumulation: once per K step, not per tile
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], u_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when((k == n_k - 1) & (n == 0))
    def _requant():
        # u epilogue: dequant + bias, then static requantize to int8 — the
        # same fp32 op order as quant_matmul's epilogue, so the fused and
        # chained paths agree bit-for-bit.  h persists in scratch across
        # the whole N sweep.
        h = acc_ref[...].astype(jnp.float32) * (sx * su_ref[...])
        h = h + bu_ref[...]
        hq_ref[...] = jnp.clip(jnp.round(h / h_scale), -h_qmax - 1.0,
                               h_qmax).astype(jnp.int8)

    @pl.when(k == n_k - 1)
    def _vstage():
        # v stage, one COUT tile: the rank-dim matmul never leaves VMEM
        acc2 = jax.lax.dot_general(
            hq_ref[...], v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = acc2.astype(jnp.float32) * (h_scale * sv_ref[...])
        y = y + bv_ref[...]
        if relu:
            y = jnp.maximum(y, 0.0)
        if out_scale is not None:
            y = jnp.clip(jnp.round(y / out_scale), -out_qmax - 1.0, out_qmax)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    'sx', 'h_scale', 'stride', 'relu', 'bm', 'bk', 'bn', 'out_dtype',
    'interpret', 'out_scale', 'h_qmax', 'out_qmax'))
def lowrank_conv(x_q, u_q, v_q, su, sv, bu, bv, *, sx, h_scale, stride=1,
                 relu=False, bm=128, bk=256, bn=128, out_dtype=jnp.float32,
                 interpret=False, out_scale=None, h_qmax=127.0,
                 out_qmax=127.0):
    """One-launch factored conv: x_q int8 (B,H,W,CIN) -> (B,OH,OW,COUT).

    u_q int8 (KH,KW,CIN,R); v_q int8 (1,1,R,COUT) (or (R,COUT)); su (R,) /
    sv (COUT,) static per-channel weight scales; bu (R,) / bv (COUT,) fp32
    biases (pass zeros when absent).  ``sx`` / ``h_scale`` / ``out_scale``
    are *static* Python floats: the input activation scale, the rank-
    intermediate requantize scale, and (optionally) the int8 output scale.
    COUT is gridded in ``bn`` tiles (any width serves); the rank must fit
    one lane tile (``fits_fused``).
    """
    B, H, W, C = x_q.shape
    kh, kw, c2, r = u_q.shape
    assert C == c2, (C, c2)
    v_q = v_q.reshape(v_q.shape[-2], v_q.shape[-1])
    r2, n = v_q.shape
    assert r == r2, (r, r2)
    patches, (oh, ow) = im2col_nhwc(x_q, kh, kw, stride)
    m = B * oh * ow
    k1 = kh * kw * C

    (bm, mp), (bk, k1p) = fit_or_pad(bm, m), fit_or_pad(bk, k1, align=LANE)
    (bn, np_) = fit_or_pad(bn, n, align=LANE)
    rp = pad_to(r)
    assert rp <= 128, (r, 'rank exceeds the fused envelope; chain instead')
    # resident per grid step: x/u/v blocks + int32 acc + int8 h + out tile
    assert (bm * bk + bk * rp + rp * bn + 4 * bm * rp + bm * rp
            + 4 * bm * bn) <= VMEM_BUDGET, (bm, bk, bn, rp)
    if (mp, k1p) != (m, k1):
        patches = jnp.pad(patches, ((0, mp - m), (0, k1p - k1)))
    u2 = jnp.pad(u_q.reshape(k1, r), ((0, k1p - k1), (0, rp - r)))
    v2 = jnp.pad(v_q, ((0, rp - r), (0, np_ - n)))
    # scales and biases ride as (1, width) rows: Mosaic cannot lay out a
    # 1-D f32 block that is not the whole array
    def row(a, width):
        a = a.astype(jnp.float32).reshape(1, -1)
        return jnp.pad(a, ((0, 0), (0, width - a.shape[1])))
    su, bu, sv, bv = row(su, rp), row(bu, rp), row(sv, np_), row(bv, np_)

    n_k = k1p // bk
    grid = (mp // bm, n_k, np_ // bn)
    if out_scale is not None:
        out_scale, out_dtype = float(out_scale), jnp.int8
    out = pl.pallas_call(
        functools.partial(_lr_kernel, n_k=n_k, sx=float(sx),
                          h_scale=float(h_scale), h_qmax=float(h_qmax),
                          relu=relu, out_scale=out_scale,
                          out_qmax=float(out_qmax)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, j: (i, k)),
            pl.BlockSpec((bk, rp), lambda i, k, j: (k, 0)),
            pl.BlockSpec((1, rp), lambda i, k, j: (0, 0)),
            pl.BlockSpec((1, rp), lambda i, k, j: (0, 0)),
            pl.BlockSpec((rp, bn), lambda i, k, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, k, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, k, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, k, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, rp), jnp.int32),
                        pltpu.VMEM((bm, rp), jnp.int8)],
        interpret=interpret,
    )(patches, u2, su, bu, v2, sv, bv)
    return out[:m, :n].reshape(B, oh, ow, n)
