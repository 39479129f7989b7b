"""From a profiler trace to device busy time, idle share and a breakdown.

The JAX profiler writes an ``.xplane.pb`` under
``<dir>/plugins/profile/<time>/``; :func:`read_xplane` turns it into plain
lists, and :func:`reduce_trace` does the arithmetic on those lists, so a
test can hand-build a trace.

* Device events: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane,
  as ``(name, start_ns, end_ns)``.
* Host events: every line of the ``/host:CPU`` plane, as ``(name,
  start_ns, end_ns)``.  The window is the host event named
  ``bench.window`` (a ``jax.profiler.TraceAnnotation`` the harness puts
  around its measured window); host events whose name starts with
  ``bench.`` label what the host was doing.

Busy time is the union of a chip's op intervals clipped to the window,
averaged over the chips; the idle share is 1 - busy / window.  Each op
name's own time (``op_s``) is the sum of its clipped intervals over every
chip, overlaps included, so a reader can hold one kernel's time against
its least time.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = 'bench.window'
DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
DEVICE_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return paths[-1]


def read_xplane(path: str) -> tuple[dict, list]:
    """``({device plane: [(name, t0_ns, t1_ns)]}, [(name, t0_ns, t1_ns)])``
    from one profiler trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    evs.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith('bench.'))
    return device, host


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(t0, t1)`` intervals as sorted, disjoint intervals."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def clip(intervals, lo, hi) -> list[tuple[float, float]]:
    return [(max(t0, lo), min(t1, hi)) for t0, t1 in intervals
            if t1 > lo and t0 < hi]


def window_bounds(host) -> tuple[float, float]:
    spans = [(t0, t1) for name, t0, t1 in host if name == WINDOW]
    if len(spans) != 1:
        raise ValueError(f'expected one {WINDOW!r} host span, found '
                         f'{len(spans)}')
    return spans[0]


NO_SPAN = 'bench.window (no host span)'


def labels_at(host, times) -> list[str]:
    """For each time in ``times`` (ascending), the innermost ``bench.*``
    host span around it.  The harness's spans are all on one thread and
    nest, so one sweep with a stack finds them."""
    spans = sorted((t0, -t1, name) for name, t0, t1 in host
                   if name != WINDOW)
    stack, i, out = [], 0, []
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            t0, neg_t1, name = spans[i]
            while stack and stack[-1][0] <= t0:
                stack.pop()
            stack.append((-neg_t1, name))
            i += 1
        while stack and stack[-1][0] <= t:
            stack.pop()
        out.append(stack[-1][1] if stack else NO_SPAN)
    return out


def reduce_trace(device: dict, host: list, top: int = 10) -> dict:
    """Busy and idle time of the chips in the window, and a breakdown.

    Returns ``busy_s`` (mean over chips), ``window_s``, ``idle_share``,
    ``n_ops``, ``op_s`` (``{name: seconds}``: every op name's device time
    in the window, summed over chips, for the per-layer readers),
    ``device_ops`` (the ``top`` of ``op_s`` as ``[[name, seconds]]``) and
    ``idle_gaps`` (idle seconds summed by the host span the gap fell in,
    the ``top`` largest).  The result line's ``breakdown`` carries
    ``device_ops`` and ``idle_gaps`` only."""
    lo, hi = window_bounds(host)
    if not device:
        raise ValueError('the trace holds no device plane')
    busy, by_op, gaps, n_ops = [], {}, {}, 0
    for plane in sorted(device):
        evs = [(n, max(t0, lo), min(t1, hi)) for n, t0, t1 in device[plane]
               if t1 > lo and t0 < hi]
        n_ops += len(evs)
        for n, t0, t1 in evs:
            by_op[n] = by_op.get(n, 0.0) + (t1 - t0) * 1e-9
        merged = merge((t0, t1) for _, t0, t1 in evs)
        busy.append(sum(t1 - t0 for t0, t1 in merged) * 1e-9)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        for (g0, g1), lab in zip(idle, labels_at(
                host, [(g0 + g1) / 2 for g0, g1 in idle])):
            gaps[lab] = gaps.get(lab, 0.0) + (g1 - g0) * 1e-9
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy)

    def top_of(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]

    return {'busy_s': busy_s, 'window_s': window_s,
            'idle_share': 1.0 - busy_s / window_s if window_s > 0 else None,
            'n_ops': n_ops, 'op_s': by_op, 'device_ops': top_of(by_op),
            'idle_gaps': top_of(gaps)}
