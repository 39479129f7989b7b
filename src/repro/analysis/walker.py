"""Shared jaxpr walking — THE one implementation tests and production rules
both use (tests/test_export.py used to carry a private copy; a drifted
walker means a contract the tests check and the analyzer enforces could
silently disagree about what is in the graph).

``walk_eqns`` recurses into every sub-jaxpr a primitive carries (pjit
bodies, scan/while bodies, custom_vjp calls, pallas_call kernel bodies), so
a count over it covers the whole compiled graph, not just the top level.
"""
from __future__ import annotations


def walk_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and, recursively, in any sub-jaxpr its
    params carry (ClosedJaxpr via ``.jaxpr``, open Jaxpr via ``.eqns``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if hasattr(v, 'jaxpr'):
                yield from walk_eqns(v.jaxpr)
            elif hasattr(v, 'eqns'):
                yield from walk_eqns(v)


def prim_count(jaxpr, name: str) -> int:
    """Number of eqns whose primitive is called ``name`` (recursive)."""
    return sum(1 for e in walk_eqns(jaxpr) if e.primitive.name == name)


def pallas_calls(jaxpr):
    """All ``pallas_call`` eqns in the graph (recursive)."""
    return [e for e in walk_eqns(jaxpr) if e.primitive.name == 'pallas_call']


def _aval_bytes(aval) -> int:
    """Bytes of an abstract value (works for MemRef/ShapedArray alike)."""
    n = 1
    for d in aval.shape:
        n *= int(d)
    return n * aval.dtype.itemsize


def pallas_call_vmem_bytes(eqn) -> int:
    """Per-grid-step VMEM-resident bytes of one ``pallas_call`` eqn.

    Sums every block mapping's block (inputs and outputs, at the operand
    dtype) plus the scratch operands (the trailing invars of the kernel
    jaxpr beyond inputs+outputs).  This is the same quantity the kernels
    size against ``tiling.VMEM_BUDGET`` at build time — recomputed here
    from the *compiled* graph, so a kernel that forgot its own fit check
    still gets caught at export.
    """
    gm = eqn.params['grid_mapping']
    # block_aval: the block's shape (squeezed dims dropped) at the operand
    # dtype — what one grid step holds in VMEM
    total = sum(_aval_bytes(bm.block_aval) for bm in gm.block_mappings)
    inner = eqn.params['jaxpr']
    n_io = gm.num_inputs + gm.num_outputs
    for v in inner.invars[n_io:]:
        total += _aval_bytes(v.aval)
    return total


def pallas_call_name(eqn) -> str:
    """The kernel's debug name ('_qmm_kernel', '_lr_kernel', ...): the
    explicit ``name=`` when one was given, else the kernel function's."""
    if eqn.params.get('name'):
        return str(eqn.params['name']).split()[0]
    info = getattr(eqn.params['jaxpr'], 'debug_info', None)
    src = getattr(info, 'func_src_info', None) or 'pallas_call'
    return src.split()[0]
