"""The 64-bit stream-id fold the sketches hash, written out for the
reference (a copy of the arithmetic, not an import of the program):
murmur3 fmix32 of the high half, times the golden ratio, xor the low
half. Identity for ids below 2**32."""
from __future__ import annotations

import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.atleast_1d(np.asarray(x)).astype(np.uint32)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
        x ^= x >> np.uint32(13)
        x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
        x ^= x >> np.uint32(16)
    return x


def fold64(ids) -> np.ndarray:
    """int64 ids -> the uint32 item identity every sketch hashes."""
    s = np.asarray(ids, np.int64)
    lo = (s & np.int64(0xFFFFFFFF)).astype(np.uint32)
    hi = ((s >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    with np.errstate(over="ignore"):
        return (lo ^ (_mix32(hi) * _GOLDEN)).astype(np.uint32)
