"""Process-stable seed derivation shared by every simulation layer.

``hash(str)`` is salted per interpreter process, so anything seeded with it
reproduces only under a pinned ``PYTHONHASHSEED``; ``zlib.crc32`` is defined
by the bytes alone.  ``dag.instantiate`` (work jitter),
``profiler.profile_node_synthetic`` (measurement noise) and
``tenancy.arrival_times`` (Poisson streams) all derive their RNG seeds here
— ``tests/test_reproducibility.py`` pins the contract across processes.
``seed_sequence_state`` and ``pcg64_state`` seed many ``default_rng``
streams at once, bit for bit as numpy does one at a time
(``tests/test_replica_draws.py``).
"""
from __future__ import annotations

import zlib

import numpy as np


def stable_seed(name: str) -> int:
    """Deterministic 16-bit seed component for a workflow/node/tenant name."""
    return zlib.crc32(name.encode()) & 0xFFFF


# ------------------------------------------------ many streams seeded at once
# numpy's ``SeedSequence`` (pool of 4 uint32 words) and ``PCG64`` seeding,
# computed for many entropy tuples in one pass.  ``np.random.default_rng(e)``
# is ``Generator(PCG64(SeedSequence(e)))``; building one per stream spends
# most of its time in this seeding, not in the draws that follow.
_POOL = 4
_INIT_A, _MULT_A = 0x43B0D7E5, 0x931E8875
_INIT_B, _MULT_B = 0x8B51F9DD, 0x58F38DED
_MIX_MULT_L, _MIX_MULT_R = 0xCA01F9DD, 0x4973F715
_MASK32 = 0xFFFFFFFF
_MASK128 = (1 << 128) - 1
PCG64_MULT = 0x2360ED051FC65DA44385DF649FCCF645


def _uint32_words(col) -> tuple[np.ndarray, np.ndarray]:
    """``[n, W]`` little-endian 32-bit words of the non-negative ints
    ``col`` and ``[n]`` the count of each, as ``SeedSequence`` splits an
    int: 0 is one word, and each further 32 bits take one more."""
    col = np.asarray(col)
    if (col < 0).any():
        raise ValueError("expected non-negative integer")
    words, count = [], np.ones(col.shape[0], np.int64)
    while True:
        words.append((col & _MASK32).astype(np.uint32))
        col = col >> 32
        more = col > 0
        if not more.any():
            return np.stack(words, axis=1), count
        count += more


def _hashmix(value, hc: list):
    """``SeedSequence``'s hashmix; ``hc[0]``, the running hash constant,
    depends on the call's position alone and is kept as a Python int."""
    value = value ^ np.uint32(hc[0])
    hc[0] = (hc[0] * _MULT_A) & _MASK32
    value = value * np.uint32(hc[0])
    return value ^ (value >> np.uint32(16))


def _mix(x, y):
    out = np.uint32(_MIX_MULT_L) * x - np.uint32(_MIX_MULT_R) * y
    return out ^ (out >> np.uint32(16))


def _pool_state(entropy: np.ndarray) -> np.ndarray:
    """``generate_state(4, np.uint64)`` of the sequences whose assembled
    entropy words are the rows of ``entropy`` (``[n, L]`` uint32, L >= 4,
    every row ``L`` words long)."""
    hc = [_INIT_A]
    pool = [_hashmix(entropy[:, i], hc) for i in range(_POOL)]
    for src in range(_POOL):
        for dst in range(_POOL):
            if src != dst:
                pool[dst] = _mix(pool[dst], _hashmix(pool[src], hc))
    for src in range(_POOL, entropy.shape[1]):
        for dst in range(_POOL):
            pool[dst] = _mix(pool[dst], _hashmix(entropy[:, src], hc))
    hash_const = _INIT_B
    out = []
    for i in range(2 * _POOL):
        v = pool[i % _POOL] ^ np.uint32(hash_const)
        hash_const = (hash_const * _MULT_B) & _MASK32
        v = v * np.uint32(hash_const)
        out.append((v ^ (v >> np.uint32(16))).astype(np.uint64))
    return np.stack([out[2 * k] | (out[2 * k + 1] << np.uint64(32))
                     for k in range(_POOL)], axis=1)


def seed_sequence_state(*columns) -> np.ndarray:
    """``[n, 4]`` uint64: row ``i`` is
    ``np.random.SeedSequence((c[i] for c in columns)).generate_state(4,
    np.uint64)``, for ``columns`` of ``n`` non-negative ints each (integer
    or object arrays).  A negative value raises ``ValueError``, as numpy
    does."""
    parts = [_uint32_words(c) for c in columns]
    n = parts[0][0].shape[0]
    total = sum(count for _, count in parts)
    entropy = np.zeros((n, max(_POOL, int(total.max()))), np.uint32)
    rows = np.arange(n)
    off = np.zeros(n, np.int64)
    for words, count in parts:
        for k in range(words.shape[1]):
            m = k < count
            entropy[rows[m], off[m] + k] = words[m, k]
        off += count
    # up to the pool's size, the words missing from a row hash as zeros;
    # past it, every row length mixes on its own
    length = np.maximum(total, _POOL)
    out = np.empty((n, _POOL), np.uint64)
    with np.errstate(over="ignore"):
        for L in np.unique(length):
            m = length == L
            out[m] = _pool_state(entropy[m, :L])
    return out


def pcg64_state(words) -> dict:
    """``np.random.PCG64(SeedSequence(e)).state`` from ``words``, the four
    ints of ``e``'s row of :func:`seed_sequence_state`: ``pcg64_set_seed``'s
    two LCG steps, with no draw buffered."""
    w0, w1, w2, w3 = words
    inc = (((w2 << 64) | w3) << 1 | 1) & _MASK128
    state = ((inc + ((w0 << 64) | w1)) * PCG64_MULT + inc) & _MASK128
    return {"bit_generator": "PCG64", "state": {"state": state, "inc": inc},
            "has_uint32": 0, "uinteger": 0}
