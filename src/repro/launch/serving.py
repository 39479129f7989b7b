"""Sharded decode attention: shard_map wrappers injected into the model ctx.

The decode caches are sequence-sharded (see launch/sharding.py); each device
computes flash-decode partials over its local cache chunk and the partials
are merged with pmax/psum (softmax-merge) across the sequence axes.  This is
what lets GQA archs whose kv_heads (1–8) don't divide the 16-way model axis
still shard their caches — and what makes the 500k-context cells fit.

The math inside the shard_map body is models/attention.decode_attn_reference
with ``axis_names`` set — identical code to the single-device reference, so
the CPU tests and the production path cannot drift apart.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


from repro.models.attention import (decode_attn_reference,
                                    decode_mla_reference)


def make_decode_ctx(mesh, cfg, *, long_ctx=False):
    """ctx dict with shard_map'd decode_attn / decode_mla."""
    dp = tuple(a for a in mesh.axis_names if a != 'model')
    seq_axes = (dp + ('model',)) if long_ctx else ('model',)
    seq_spec = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    bspec = None if long_ctx else (dp if len(dp) > 1 else dp[0])

    def cache_specs(cache):
        """Spec pytree for a layer cache dict: seq dim sharded."""
        def one(path, leaf):
            key = str(getattr(path[-1], 'key', ''))
            if key in ('k', 'v', 'k_s', 'v_s', 'ckv', 'kr'):
                return P(bspec, seq_spec)
            if key in ('slots', 'pos'):
                return P(seq_spec)
            return P()
        import jax
        return jax.tree_util.tree_map_with_path(one, cache)

    def decode_attn(q, nk, nv, cache, cur, *, window=0, attn_softcap=0.0):
        def local(q, nk, nv, cache, cur):
            return decode_attn_reference(q, nk, nv, cache, cur,
                                         window=window,
                                         attn_softcap=attn_softcap,
                                         axis_names=seq_axes)
        cs = cache_specs(cache)
        fn = shard_map(local, mesh,
                       in_specs=(P(bspec), P(bspec), P(bspec), cs, P()),
                       out_specs=(P(bspec), cs))
        return fn(q, nk, nv, cache, cur)

    def decode_mla(q_lat, q_rope, new_ckv, new_kr, cache, cur):
        def local(q_lat, q_rope, new_ckv, new_kr, cache, cur):
            return decode_mla_reference(q_lat, q_rope, new_ckv, new_kr,
                                        cache, cur, axis_names=seq_axes)
        cs = cache_specs(cache)
        fn = shard_map(local, mesh,
                       in_specs=(P(bspec), P(bspec), P(bspec), P(bspec),
                                 cs, P()),
                       out_specs=(P(bspec), cs))
        return fn(q_lat, q_rope, new_ckv, new_kr, cache, cur)

    return {'decode_attn': decode_attn, 'decode_mla': decode_mla}
