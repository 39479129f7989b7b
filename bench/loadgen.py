"""The one traffic generator: images from the seed, and the two load loops
a traffic file can name (``"mode"``).

Images are class templates, smoothed and normalised, rolled by a small
random shift, scaled and noised: the generator of the program's
``data/synthetic.py`` ``SyntheticImages``, copied here so that the
yardstick does not move with the program.  They are made on the device in
one jitted call and handed to the clients as host ``numpy`` float32 rows,
so the served path does the upload, as for images that arrive over the
network.

Load loops, both timed on the host's wall clock around the entry point
``ContinuousBatchScheduler.run_trace``:

* ``backlog``: consecutive chunks of ``chunk`` requests, all due at the
  chunk's start, until ``seconds`` have passed.  Every chunk sends the
  pool in the same seed-drawn order, so every chunk runs the same batch
  shapes and one warm-up chunk warms all of them.
* ``closed``: one client, one request at a time; the next is sent when the
  answer is back on the host.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp


def make_images(key, n, *, size, channels, classes, jitter, difficulty,
                scale_sd):
    """``n`` float32 images (n, size, size, channels) from ``key``."""
    kt, ky, ks, kn, kc = jax.random.split(key, 5)
    t = jax.random.normal(kt, (classes, size, size, channels), jnp.float32)
    for _ in range(2):      # smooth, so small receptive fields see shapes
        t = (t + jnp.roll(t, 1, 1) + jnp.roll(t, -1, 1)
             + jnp.roll(t, 1, 2) + jnp.roll(t, -1, 2)) / 5.0
    t = t / t.std()
    y = jax.random.randint(ky, (n,), 0, classes)
    shift = jax.random.randint(ks, (n, 2), -jitter, jitter + 1)
    base = jax.vmap(lambda img, s: jnp.roll(img, s, axis=(0, 1)))(t[y], shift)
    noise = jax.random.normal(kn, base.shape, jnp.float32) * difficulty
    scale = 1.0 + scale_sd * jax.random.normal(kc, (n, 1, 1, 1), jnp.float32)
    return base * scale + noise


def _no_span(name):
    return contextlib.nullcontext()


def run_backlog(sched, pool, order, chunk, seconds, request_cls,
                span=_no_span):
    """Chunks of ``chunk`` requests (pool rows ``order[:chunk]``, request
    id = position in the chunk), each served whole, until ``seconds`` have
    passed.  Returns ``{'chunks': [(completions, metrics)], 'window_s',
    'attempted'}``; the window runs from the first chunk's hand-over to the
    last chunk's drain."""
    rows = order[:chunk]
    chunks = []
    t0 = time.perf_counter()
    while True:
        with span('bench.chunk'):
            reqs = [request_cls(i, pool[j], 0.0) for i, j in enumerate(rows)]
            chunks.append(sched.run_trace(reqs))
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    return {'chunks': chunks, 'window_s': t1 - t0,
            'attempted': len(chunks) * len(rows)}


def run_closed(sched, pool, order, seconds, request_cls, span=_no_span,
               n=None):
    """One client: request ``i`` carries pool row ``order[i % len]``, sent
    when the answer to ``i - 1`` is back.  Runs ``n`` requests, or until
    ``seconds`` have passed.  Returns ``{'answers': [(pool row, completion
    or None)], 'latency_s': [...], 'window_s', 'attempted'}``."""
    answers, lat = [], []
    t0 = time.perf_counter()
    i = 0
    while (n is None and time.perf_counter() - t0 < seconds) or (
            n is not None and i < n):
        j = order[i % len(order)]
        with span('bench.request'):
            ts = time.perf_counter()
            comp, _ = sched.run_trace([request_cls(i, pool[j], 0.0)])
            lat.append(time.perf_counter() - ts)
        answers.append((j, comp.get(i)))
        i += 1
    return {'answers': answers, 'latency_s': lat,
            'window_s': time.perf_counter() - t0, 'attempted': i}
