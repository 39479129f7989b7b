"""Fixed-point uniform quantization-aware training (paper's Q pass).

Follows DoReFa-style fixed-point uniform QAT (Zhou et al., 2016): symmetric
per-channel weight quantization + unsigned activation quantization after a
learned-free clip, with straight-through estimators.  This module is pure
jnp — it is both the math used inside the models (fake-quant hook on every
matmul) and the oracle for the Pallas ``fake_quant`` / ``quant_matmul``
kernels.

The actual *pass* object (QuantizationPass) lives in core/passes.py; it sets
``cfg.w_bits / cfg.a_bits`` and runs QAT fine-tuning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _ste(x_q: jax.Array, x: jax.Array) -> jax.Array:
    """Straight-through estimator: forward x_q, gradient of identity."""
    return x + jax.lax.stop_gradient(x_q - x)


# Counts weight abs-max (scale) computations, including under tracing.  The
# export tests use it to prove the exported serving function recomputes NO
# weight scales per call: tracing the serving fn must leave it unchanged,
# while tracing a fake-quant forward bumps it once per weight.
WEIGHT_SCALE_COMPUTATIONS = [0]


def quantize_weight(w: jax.Array, bits: int, *, axis=-1):
    """Symmetric per-channel int quantization. Returns (int_values, scale).

    ``axis`` is the axis (or tuple of axes) that keep their own scale
    (None = per-tensor).  bits=1 follows DoReFa binary weights
    (sign * mean|w|).  This is the single weight quantizer — QAT
    (fake_quant_weight) and serving export (quantize_params_for_serving,
    ops.prequantize_weight) all route here, so grids cannot drift.
    """
    WEIGHT_SCALE_COMPUTATIONS[0] += 1
    if axis is None:
        red = None
    else:
        kept = {a % w.ndim for a in
                ((axis,) if isinstance(axis, int) else tuple(axis))}
        red = tuple(i for i in range(w.ndim) if i not in kept)
    if bits == 1:
        scale = jnp.mean(jnp.abs(w), axis=red, keepdims=True)
        q = jnp.sign(w)
        q = jnp.where(q == 0, 1.0, q)
        return q.astype(jnp.int8), scale
    qmax = 2.0 ** (bits - 1) - 1.0
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=red is not None)
    # times the reciprocal, not divided by qmax: XLA rewrites a division by
    # a constant into this multiply inside jit, so writing it out keeps the
    # eager export and a jitted forward on the same scale bits
    scale = jnp.maximum(amax, 1e-8) * (1.0 / qmax)
    q = jnp.clip(jnp.round(w / scale), -qmax - 1, qmax)
    return q.astype(jnp.int8 if bits <= 8 else jnp.int32), scale


def fake_quant_weight(w: jax.Array, bits: int, *, axis: int | None = -1,
                      use_kernel: bool | None = None) -> jax.Array:
    """Quantize->dequantize with STE (QAT forward for weights).

    On accelerators the 2D last-axis case routes to the fused Pallas
    fake-quant kernel (kernels/fake_quant.py — one HBM pass instead of
    XLA's materialized abs/max/round chain); the STE makes the kernel's
    gradient irrelevant (stop_gradient), so no custom VJP is needed.  CPU
    (and odd shapes/axes, and the bits=1 DoReFa grid) stay on pure jnp.
    """
    if bits <= 0 or bits >= 32:
        return w
    if use_kernel is None:
        use_kernel = (jax.default_backend() == 'tpu' and w.ndim == 2
                      and bits > 1 and axis in (-1, 1))
    if use_kernel:
        return _kernel_fake_quant_ste(w, bits)
    codes, scale = fake_quant_weight_codes(w, bits, axis=axis)
    return codes * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _kernel_fake_quant_ste(w, bits):
    # STE via custom_vjp: autodiff never traces into pallas_call
    from repro.kernels.ops import fake_quant as _kernel_fq
    return _kernel_fq(w, bits).astype(w.dtype)


def _kfq_fwd(w, bits):
    return _kernel_fake_quant_ste(w, bits), None


def _kfq_bwd(bits, _res, g):
    return (g,)


_kernel_fake_quant_ste.defvjp(_kfq_fwd, _kfq_bwd)


def fake_quant_weight_codes(w: jax.Array, bits: int, *, axis=-1):
    """Weight fake-quant split as (codes, scale), on :func:`quantize_weight`'s
    grid: ``codes * scale`` is :func:`fake_quant_weight`'s value.  The codes
    are exact integers carrying the STE gradient; the scale (broadcast
    shape) is constant to autodiff.  ``bits`` of 0 or >= 32 give ``(w, 1)``.
    """
    if bits <= 0 or bits >= 32:
        return w, jnp.ones((), w.dtype)
    q, scale = quantize_weight(w, bits, axis=axis)
    scale = jax.lax.stop_gradient(scale.astype(w.dtype))
    codes = _ste(q.astype(w.dtype), w / scale)
    # opaque to XLA's algebraic simplifier, like the export's precomputed
    # scale: otherwise it reassociates (amax_x * c) * (amax_w * c) in the
    # conv epilogue and rounds the product differently from serving
    return codes, jax.lax.optimization_barrier(scale)


def fake_quant_act_codes(x: jax.Array, bits: int, *,
                         amax: float | None = None):
    """Activation fake-quant split as (codes, scale): symmetric uniform with
    a per-tensor scale from ``amax`` (default: the batch's abs-max, the
    paper's hardware-friendly 'fixed-point uniform' choice).  The scale is
    constant to autodiff, which keeps QAT stable; the codes carry the STE
    gradient.  ``bits`` of 0 or >= 32 give ``(x, 1)``."""
    if bits <= 0 or bits >= 32:
        return x, jnp.ones((), x.dtype)
    qmax = 2.0 ** (bits - 1) - 1.0
    s = jnp.max(jnp.abs(x)) if amax is None else jnp.asarray(amax, x.dtype)
    # times the reciprocal, as in quantize_weight
    s = jax.lax.stop_gradient(jnp.maximum(s, 1e-8)) * (1.0 / qmax)
    v = x / s
    return _ste(jnp.clip(jnp.round(v), -qmax - 1, qmax), v), s


def fake_quant_act(x: jax.Array, bits: int, *, amax: float | None = None) -> jax.Array:
    """Activation fake-quant with STE: ``codes * scale`` of
    :func:`fake_quant_act_codes`."""
    if bits <= 0 or bits >= 32:
        return x
    codes, s = fake_quant_act_codes(x, bits, amax=amax)
    return (codes * s).astype(x.dtype)


def quantize_params_for_serving(params, bits: int = 8):
    """Convert every matmul/conv weight to int8 + per-out-channel scales.

    The serving-side realization of the paper's Q pass: weights are stored
    (and read from HBM) as int8, halving the weight-streaming bytes that
    dominate memory-bound decode.  ``layers.dense`` recognizes the
    {'w_q','scale'} form and dequantizes in-register; the exported CNN path
    (core/export.py) feeds the int8 form directly to the Pallas
    quant_matmul/quant_conv kernels.  Covered weights: 2D dense (d,f),
    scan-stacked 3D (G,d,f), 4D NHWC conv (KH,KW,CIN,COUT) — conv scales
    are stored flat (COUT,) as the quant_conv kernel consumes them.
    Embedding tables (lookups), norm scales, and recurrent conv1d taps
    (under the 'conv' key — elementwise, not matmuls) are left untouched.
    """
    def quant(v, flat_scale=False):
        # one quantizer (quantize_weight) for QAT and serving, so the
        # bits=1 DoReFa branch and clip conventions cannot drift
        v = v.astype(jnp.float32)
        if flat_scale:               # conv (KH,KW,CIN,COUT): (COUT,) scales
            q, scale = quantize_weight(v, bits, axis=-1)
            scale = scale.reshape(-1)
        else:                        # dense (d,f) / stacked (G,d,f): keep
            kept = tuple(i for i in range(v.ndim) if i != v.ndim - 2)
            q, scale = quantize_weight(v, bits, axis=kept)
        return q.astype(jnp.int8), scale.astype(jnp.float32)

    def convert(node, name=''):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                # matmul weights: 2D (d,f) or scan-stacked 3D (G,d,f)
                if name != 'conv' and k == 'w' and hasattr(v, 'ndim') \
                        and v.ndim in (2, 3):
                    q, s = quant(v)
                    out['w_q'], out['scale'] = q, s
                # NHWC conv weights (KH,KW,CIN,COUT): flat (COUT,) scales
                elif k == 'w' and hasattr(v, 'ndim') and v.ndim == 4:
                    q, s = quant(v, flat_scale=True)
                    out['w_q'], out['scale'] = q, s
                # MoE expert weights: (E,d,f) or stacked (G,E,d,f)
                elif k in ('wi', 'wg', 'wo') and hasattr(v, 'ndim') \
                        and getattr(v, 'ndim', 0) in (3, 4) \
                        and not isinstance(v, dict):
                    q, s = quant(v)
                    out[k] = {'w_q': q, 'scale': s}
                else:
                    out[k] = convert(v, k)
            return out
        if isinstance(node, list):
            return [convert(v, name) for v in node]
        if isinstance(node, tuple):
            return tuple(convert(v, name) for v in node)
        return node

    return convert(params)


def quantized_params_bits(params, bits: int) -> int:
    """Total storage bits for a params pytree at `bits` per weight."""
    leaves = jax.tree_util.tree_leaves(params)
    n = sum(x.size for x in leaves)
    return n * bits
