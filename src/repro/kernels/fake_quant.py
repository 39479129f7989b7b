"""Fused per-channel fake-quantization Pallas kernels (the QAT hot op).

QAT evaluates quantize→dequantize on every weight every step.  XLA's naive
lowering materializes abs/max/round intermediates in HBM; here there are two
strategies:

* :func:`fake_quant` — two VMEM-tiled kernels (reduction kernel accumulates
  per-column amax across K tiles; quantize kernel is a single elementwise
  sweep with the (1, bn) scale row resident in VMEM).  W streams through
  HBM twice (amax read + quantize read/write).
* :func:`fake_quant_fused` — single-pass variant: each grid step holds a
  full (K, bn) column stripe in VMEM, computes the per-column amax and
  quantizes in one sweep, so W is read from HBM exactly once.  Use it when
  the stripe fits VMEM (K * bn * 4B ≲ a few MB — true for every weight in
  this repo); fall back to the two-kernel version for huge K.

Awkward dims are zero-padded to the next 128 multiple and sliced back
(zero rows never win the abs-max; see kernels/tiling.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANE, fit_or_pad


def _amax_kernel(w_ref, o_ref, *, n_k):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = jnp.maximum(o_ref[...],
                             jnp.max(jnp.abs(w_ref[...]), axis=0,
                                     keepdims=True))


def _quant_kernel(w_ref, amax_ref, o_ref, *, qmax):
    scale = jnp.maximum(amax_ref[...], 1e-8) / qmax      # (1, bn) row
    w = w_ref[...] / scale
    o_ref[...] = (jnp.clip(jnp.round(w), -qmax - 1, qmax)
                  * scale).astype(o_ref.dtype)


def _fused_kernel(w_ref, o_ref, *, qmax):
    w = w_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax - 1, qmax)
    o_ref[...] = (q * scale[None, :]).astype(o_ref.dtype)


def _pad2(w, K, N, Kp, Np):
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    return w


@functools.partial(jax.jit, static_argnames=('bits', 'bk', 'bn', 'interpret'))
def fake_quant(w, *, bits=8, bk=512, bn=256, interpret=False):
    """Per-output-channel (last-dim) symmetric fake quant of w (K, N)."""
    K, N = w.shape
    (bk, Kp), (bn, Np) = fit_or_pad(bk, K), fit_or_pad(bn, N, align=LANE)
    w = _pad2(w, K, N, Kp, Np)
    qmax = 2.0 ** (bits - 1) - 1.0
    amax = pl.pallas_call(
        functools.partial(_amax_kernel, n_k=Kp // bk),
        grid=(Np // bn, Kp // bk),
        in_specs=[pl.BlockSpec((bk, bn), lambda j, k: (k, j))],
        out_specs=pl.BlockSpec((1, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(Kp // bk, Np // bn),
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Kp, Np), w.dtype),
        interpret=interpret,
    )(w, amax)
    return out[:K, :N] if (Kp, Np) != (K, N) else out


@functools.partial(jax.jit, static_argnames=('bits', 'bn', 'interpret'))
def fake_quant_fused(w, *, bits=8, bn=256, interpret=False):
    """Single-pass fake quant: one HBM read of W instead of two.

    Holds a full (K, bn) column stripe in VMEM per grid step, so the amax
    reduction and the rounding sweep fuse into one kernel.
    """
    K, N = w.shape
    bn, Np = fit_or_pad(bn, N, align=LANE)
    w = _pad2(w, K, N, K, Np)
    qmax = 2.0 ** (bits - 1) - 1.0
    out = pl.pallas_call(
        functools.partial(_fused_kernel, qmax=qmax),
        grid=(Np // bn,),
        in_specs=[pl.BlockSpec((K, bn), lambda j: (0, j))],
        out_specs=pl.BlockSpec((K, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((K, Np), w.dtype),
        interpret=interpret,
    )(w)
    return out[:, :N] if Np != N else out
