"""Packed bin indices: nibble pairs at ≤16 bins, single bytes at ≤256.

The PR 9 leftover (ROADMAP item 3): the persistent binned matrix is the
largest training-resident array.  Two lossless packing tiers:

- **Nibble tier** (``num_bins ≤ 16``, ``max_bin ≤ 15``): every index
  fits 4 bits — packing consecutive ROW pairs of a column into one byte
  halves the binned cache's HBM/upload bytes.  Row-pair (not
  column-pair) packing keeps the feature axis intact, so per-feature
  metadata (categorical masks, bounds) is untouched.  The streamed
  cache (``data/streaming.py``) unpacks it once, before a fit.

- **Byte tier** (``16 < num_bins ≤ 256``, i.e. through the default
  ``max_bin=255``): every index fits ONE byte, so the packed form is
  simply uint8 (:func:`pack_bytes` / :func:`unpack_bytes` exist for
  contract symmetry and range checking).  The win here is not the
  row-major cache — ``BinMapper`` already emits uint8 — but the
  GROWERS' transposed (F, n) working set, which historically widened to
  int32 (4 bytes/index) for the histogram kernels.
  :func:`hist_transpose` is the single authority for that layout: it
  keeps the transposed matrix uint8 whenever the byte tier applies and
  the Pallas/scatter kernels widen per block/chunk INSIDE their
  bodies, so HBM holds (and every hist pass DMAs) 1-byte indices — a 4×
  cut in the hist-pass working set at 255 bins.

(The old "honest scope note": the ROADMAP wording "63-bin indices two
per byte" does not fit arithmetic — 63 value bins + missing = 64 bins
need 6 bits.  Between 17 and 256 bins the byte tier is the lossless
floor; nibble packing engages only below it, gated by
:func:`can_pack`.)  Both tiers are exact (``unpack_rows(pack_rows(b),
n) == b`` and ``unpack_bytes(pack_bytes(b)) == b`` bit-for-bit), so
split selection from a packed cache is bitwise-identical — tested in
``tests/test_streaming.py`` and ``tests/test_binpack_bytes.py``.

All helpers are dual-backend: they use only ufunc-style operators, so
numpy arrays stay numpy and jax arrays trace/jit (the streamed cache
unpacks on the device).
"""

from __future__ import annotations

import numpy as np

PACK_MAX_BINS = 16  # 4 bits per index
BYTE_MAX_BINS = 256  # 8 bits per index (max_bin=255 + missing bin)


def can_pack(num_bins: int) -> bool:
    """True when every bin index (incl. the missing bin) fits a nibble."""
    return 0 < num_bins <= PACK_MAX_BINS


def can_pack_bytes(num_bins: int) -> bool:
    """True when every bin index (incl. the missing bin) fits one byte."""
    return 0 < num_bins <= BYTE_MAX_BINS


def packed_rows(n_rows: int) -> int:
    """Row count of the packed representation of ``n_rows`` rows."""
    return (int(n_rows) + 1) // 2


def pack_rows(bins):
    """(n, F) bin indices (< 16) → (⌈n/2⌉, F) uint8 nibble pairs.

    Row ``2i`` lands in the LOW nibble, row ``2i+1`` in the HIGH nibble.
    Odd ``n`` pads a phantom all-zero row into the final high nibble —
    callers must remember the true row count (:func:`unpack_rows` takes
    it back explicitly).
    """
    n = bins.shape[0]
    if n % 2:
        if isinstance(bins, np.ndarray):
            pad = np.zeros((1,) + bins.shape[1:], bins.dtype)
            bins = np.concatenate([bins, pad], axis=0)
        else:
            import jax.numpy as jnp

            bins = jnp.concatenate(
                [bins, jnp.zeros((1,) + bins.shape[1:], bins.dtype)], axis=0
            )
    lo = bins[0::2]
    hi = bins[1::2]
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(np.uint8)


def pack_bytes(bins):
    """(n, F) bin indices (< 256) → (n, F) uint8 — the byte-tier pack.

    A pure dtype narrowing (no layout change): the point is the
    CONTRACT — callers that pack must have ``num_bins ≤ BYTE_MAX_BINS``
    (checked here on numpy inputs, where it is free) so the narrowing is
    lossless and :func:`unpack_bytes` is an exact inverse.
    """
    if isinstance(bins, np.ndarray):
        if bins.size and (bins.min() < 0 or bins.max() >= BYTE_MAX_BINS):
            raise ValueError(
                f"bin indices outside [0, {BYTE_MAX_BINS}) cannot byte-pack"
            )
        return bins.astype(np.uint8)
    return bins.astype(np.uint8)  # jax: traced, range is the caller's contract


def unpack_bytes(packed):
    """Inverse of :func:`pack_bytes` — uint8 indices are already the
    canonical consumable form, so this is the identity (kept for
    contract symmetry with the nibble tier)."""
    return packed


def hist_transpose(bins, num_bins: int):
    """(n, F) integer bins → (F, n) in the NARROWEST lossless dtype.

    The single authority for the growers' transposed working set: uint8
    whenever the byte tier applies (``num_bins ≤ BYTE_MAX_BINS`` — one
    byte per index in HBM, widened per block inside the hist kernels),
    int32 otherwise.  Dual-backend (numpy in, numpy out; jax in,
    traced/jit out).
    """
    dtype = np.uint8 if can_pack_bytes(num_bins) else np.int32
    return bins.astype(dtype).T


def unpack_rows(packed, n_rows: int):
    """(m, F) nibble pairs → (n_rows, F) uint8 bin indices (inverse of
    :func:`pack_rows`; works on device inside jit)."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    if isinstance(packed, np.ndarray):
        out = np.empty((2 * packed.shape[0],) + packed.shape[1:], np.uint8)
        out[0::2] = lo
        out[1::2] = hi
    else:
        import jax.numpy as jnp

        out = jnp.stack([lo, hi], axis=1).reshape(
            (2 * packed.shape[0],) + tuple(packed.shape[1:])
        ).astype(jnp.uint8)
    return out[:n_rows]
