"""Plain reference of what the checkpoint path computes on the device.

Written from the published semantics in numpy, importing nothing of the
program: the gear-hash content-defined chunking (a cut after byte i when the
hash of the 32 bytes ending at i has its low bits zero, subject to a least
and a largest chunk size) and the 128-bit fingerprint of each chunk's row
(the chunk's bytes as little-endian words, its length in the word after,
zero padding to a multiple of 128 words, then a position-salted
multilinear murmur-style mix in four lanes). A leaf is chunked in segments
of whole rows, each chunked on its own, so a segment's last bytes form a
chunk of their own.
"""

from __future__ import annotations

import numpy as np

WINDOW = 32
_A = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F], np.uint32)
_B = np.array([0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09], np.uint32)
_C = np.array([0x94D049BB, 0xBF58476D, 0x2545F491, 0x9E3779B9], np.uint32)


def _gear() -> np.ndarray:
    out, x = [], 0x243F6A88
    for _ in range(256):
        x = (x + 0x9E3779B9) & 0xFFFFFFFF
        z = ((x ^ (x >> 16)) * 0x85EBCA6B) & 0xFFFFFFFF
        z = ((z ^ (z >> 13)) * 0xC2B2AE35) & 0xFFFFFFFF
        out.append(z ^ (z >> 16))
    return np.array(out, np.uint32)


GEAR = _gear()


def chunk_params(target: int) -> tuple[int, int, int]:
    """(mask, least, largest) of the checkpoint's device chunking for a
    target size: the mask keeps the bits below the target's top bit, and
    chunks hold from half the target to twice it."""
    mask = (1 << max(1, target.bit_length() - 1)) - 1
    return mask, max(1, target // 2), target * 2


def window_hashes(buf: np.ndarray) -> np.ndarray:
    """h[i] = sum_k GEAR[buf[i-k]] << k over k < 32 (mod 2**32); bytes
    before the stream count as absent."""
    # Doubling: the hash of the m bytes ending at i, plus that of the m
    # bytes before them shifted left by m, is the hash of 2m bytes.
    h = GEAR[buf]
    m = 1
    while m < WINDOW:
        h[m:] = h[m:] + (h[:-m] << np.uint32(m))
        m *= 2
    return h


def cuts(buf: np.ndarray, mask: int, least: int, largest: int) -> list[int]:
    """Inclusive end of every chunk but the tail, walking byte by byte in
    spirit: from a chunk start s, the first position i >= s + least whose
    hash has (h & mask) == 0, or s + largest - 1 if none comes first."""
    n = buf.size
    cand = np.flatnonzero((window_hashes(buf) & np.uint32(mask)) == 0)
    out, s = [], 0
    while s + least < n:
        lo = s + least
        hard = max(lo, s + largest - 1)
        j = np.searchsorted(cand, lo)
        cut = int(cand[j]) if j < cand.size and cand[j] <= hard else hard
        if cut >= n:
            break
        out.append(cut)
        s = cut + 1
    return out


def cuts_walk(buf: np.ndarray, mask: int, least: int, largest: int,
              piece: int = 1 << 16) -> list[int]:
    """``cuts``, computed as the walk goes: from each chunk start it hashes
    only from ``least`` bytes on, in pieces that stay in the CPU's cache,
    until the first candidate. Same answer, about ten times faster on
    large streams."""
    n, out, s, m32 = buf.size, [], 0, np.uint32(mask)
    while s + least < n:
        a, hard, cut = s + least, s + largest - 1, None
        while a <= hard and a < n:
            b = min(a + piece, hard + 1, n)
            lo = max(0, a - (WINDOW - 1))
            h = GEAR[buf[lo:b]]
            m = 1
            while m < WINDOW:
                h[m:] += h[:-m] << np.uint32(m)
                m *= 2
            hit = np.flatnonzero((h[a - lo :] & m32) == 0)
            if hit.size:
                cut = a + int(hit[0])
                break
            a = b
        cut = max(s + least, hard) if cut is None else cut
        if cut >= n:
            break
        out.append(cut)
        s = cut + 1
    return out


def segments(nbytes: int, unit: int, segment_bytes: int) -> list[tuple[int, int]]:
    """(start, end) byte ranges of a leaf's segments: whole units (a row of
    the leaf's (-1, last dim) view, or an element of a 0-D or 1-D leaf), at
    most ``segment_bytes`` each and one unit at least."""
    step = max(1, segment_bytes // unit) * unit
    return [(a, min(a + step, nbytes)) for a in range(0, nbytes, step)]


def segment_chunks(seg: np.ndarray, target: int) -> list[tuple[int, int]]:
    """(start, end) of every chunk of one segment, its tail included."""
    mask, least, largest = chunk_params(target)
    ends = cuts_walk(seg, mask, least, largest)
    if not ends or ends[-1] < seg.size - 1:
        ends.append(seg.size - 1)
    starts = [0] + [e + 1 for e in ends[:-1]]
    return [(a, e + 1) for a, e in zip(starts, ends)]


def _mix(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def row_width(largest: int) -> tuple[int, int]:
    payload = -(-largest // 4)
    width = payload + 1
    return payload, max(128, width + (-width) % 128)


def fingerprint(chunk: bytes, largest: int) -> bytes:
    """16 bytes: the four uint32 lanes of the chunk's row fingerprint, in
    the order and byte order the device writes them (little-endian)."""
    payload, width = row_width(largest)
    row = np.zeros(width, np.uint32)
    row[: -(-len(chunk) // 4)] = np.frombuffer(chunk + bytes(-len(chunk) % 4), "<u4")
    row[payload] = len(chunk)
    pos = np.arange(1, width + 1, dtype=np.uint32)
    with np.errstate(over="ignore"):
        acc = _mix(row[:, None] * _A[None, :] + pos[:, None] * _B[None, :]).sum(
            axis=0, dtype=np.uint32)
        acc = acc + np.uint32(width) * _C
        return _mix(acc).astype("<u4").tobytes()
