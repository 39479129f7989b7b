"""Shared block-fitting for the Pallas kernels (one copy, not N).

Every kernel here tiles a dim into equal blocks, so the block size must
divide the dim.  Mosaic (the TPU kernel compiler) adds a second rule: a
block's last two dims must be multiples of the (8, 128) sublane/lane tile
or span the whole array dim — a 192-wide lane block over K=576 is refused
at compile time even though interpret mode runs it.  The shared policy:

* :func:`fit_block` returns the whole dim when it fits in one block, else
  the largest divisor <= the requested block that is a multiple of
  ``align`` (``LANE`` for lane-axis dims, ``SUBLANE`` for row dims), and
  *raises* when there is none instead of emitting sliver or misaligned
  tiles.
* :func:`pad_to` gives the next multiple of 128 (the TPU lane width);
  kernel entry points zero-pad awkward dims up to it and slice the result
  back, so callers never see the error for value-preserving paddings.
"""
from __future__ import annotations

LANE = 128          # TPU lane width: last-dim tiles are always 128 wide
SUBLANE = 8         # TPU sublane width: second-minor tiles pack 8 rows

# THE shared VMEM budget every kernel in this package sizes its resident
# blocks against (conservative half of a v5e core's ~16 MiB, leaving room
# for double buffering).  One constant, not N per-kernel copies: a kernel
# that needs operands resident across grid steps (lowrank_conv's v block,
# fake_quant's fused column stripe, depthwise_conv's spatial plane) checks
# against this and falls back / grids further instead of silently spilling.
VMEM_BUDGET = 8 * 2 ** 20

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud TPU documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect over 4 links, i.e. ~50 GB/s per link).  A TPU
# kind missing here is an error (:func:`device_peaks`), never a default.
DEVICE_PEAKS = {
    'TPU v5 lite': {'bf16_flops': 197e12, 'int8_ops': 394e12,
                    'hbm_bytes_per_s': 819e9, 'hbm_bytes': 16e9,
                    'ici_link_bytes_per_s': 50e9},
}
MODELLED_KIND = 'TPU v5 lite'   # the row cost models use off the chip


def device_peaks(device=None) -> dict:
    """The peak row for ``device`` (default: ``jax.devices()[0]``).

    On a TPU the row is looked up by ``device_kind`` and a kind missing
    from :data:`DEVICE_PEAKS` raises ``KeyError``.  Off the chip (CPU) the
    v5e row is returned with ``modelled=True``: cost models may price a
    v5e geometry there, but nothing derived from it is a device number.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != 'tpu':
        return dict(DEVICE_PEAKS[MODELLED_KIND], kind=MODELLED_KIND,
                    modelled=True)
    kind = device.device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(f'no published peaks for TPU kind {kind!r}; add a '
                       f'sourced row to tiling.DEVICE_PEAKS')
    return dict(DEVICE_PEAKS[kind], kind=kind, modelled=False)


def pad_to(dim: int, mult: int = LANE) -> int:
    """Next multiple of ``mult`` >= dim (dim itself when it already is)."""
    return -(-dim // mult) * mult


def batch_slots(n: int, mult: int = SUBLANE) -> int:
    """Serving batch geometry: the slot count for ``n`` concurrent requests.

    The im2col int8 matmuls tile M = B*OH*OW, so the batch dim lands on the
    sublane axis — a batch that is a multiple of 8 keeps every M tile
    rectangular.  The request batcher (repro/serving/) pads its slot count
    up to this and keeps it FIXED across rounds: one compiled program per
    stage (no per-occupancy retraces), and per-slot results independent of
    how the other slots are filled (the scheduler's bit-exactness
    contract).
    """
    return pad_to(max(int(n), 1), mult)


def fit_block(block: int, dim: int, *, align: int = SUBLANE) -> int:
    """Block size for tiling ``dim`` with blocks of at most ``block``.

    ``dim`` itself when it fits in one block (a whole-dim block is always
    legal); otherwise the largest divisor of ``dim`` that is a multiple of
    ``align`` and <= ``block`` (rounded down to ``align``, at least one
    ``align`` tile).  Raises ValueError when no such divisor exists — e.g.
    prime dims, or K=576 with 128-aligned blocks of <= 256.  Callers pad
    the dim to ``pad_to(dim)`` first (the kernel wrappers in this package
    do, via :func:`fit_or_pad`).
    """
    if dim <= 0:
        raise ValueError(f'cannot tile empty dim {dim}')
    if dim <= block:
        return dim
    b = max(block - block % align, align)
    while b >= align and dim % b:
        b -= align
    if b < align:
        raise ValueError(
            f'no usable block <= {block} for dim {dim} aligned to {align}; '
            f'pad the dim to {pad_to(dim)} (next multiple of {LANE})')
    return b


def fit_or_pad(block: int, dim: int, *,
               align: int = SUBLANE) -> tuple[int, int]:
    """(block, padded_dim): like :func:`fit_block`, but instead of raising,
    returns the block for the 128-padded dim (padded_dim == dim when the
    original dim already tiles cleanly)."""
    try:
        return fit_block(block, dim, align=align), dim
    except ValueError:
        p = pad_to(dim)
        return fit_block(block, p, align=align), p
