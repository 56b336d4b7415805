"""The bytes each kernel of one mine must move, from the table's shape.

The key layout is the program's bit-width plan, re-derived here from
the paper's packed-key construction: mode k's sort key is the other
columns, the value lane (NOAC) and e_k, each entity field
``ceil(log2 |A_j|)`` bits wide; a value lane over a known domain of D
distinct values is ``ceil(log2 D)`` bits.  Keys of up to 32 bits are one
uint32 word, keys of up to 64 bits two.  Stage 3 sorts the 64-bit
cluster signature (two words, every bit live).

The radix sort on the chip runs 8-bit digits: one histogram sweep per
sort reads every key word once and writes one 256-bucket int32 row per
pass; each pass's rank kernel reads the pass's uint32 digit and the 256
bucket starts, and writes one int32 rank per row.  The Stage 2
segment-reduce kernel reads two uint32 weight lanes and the int32
first-occurrence flags and writes three int32 prefix sums, once per
mode.  Counts are of the T rows of the table, not of padded buffers.
"""
from __future__ import annotations

import math

DIGIT_BITS = 8
BUCKETS = 1 << DIGIT_BITS
WORD = 4                      # bytes of a uint32 / int32


def entity_bits(size: int) -> int:
    return max(1, math.ceil(math.log2(max(int(size), 2))))


def key_bits(sizes, value_slots=None) -> int:
    """Live bits of every mode's Stage 1 key (the same for all modes)."""
    bits = sum(entity_bits(s) for s in sizes)
    if value_slots is not None:
        bits += entity_bits(value_slots)
    return bits


def radix_sort_bytes(t: int, live_bits: int) -> int:
    """HBM bytes of one histogram-rank radix sort of ``t`` keys."""
    words = 1 if live_bits <= 32 else 2
    passes = math.ceil(live_bits / DIGIT_BITS)
    hist = words * WORD * t + passes * BUCKETS * WORD
    rank = passes * (2 * WORD * t + BUCKETS * WORD)
    return hist + rank


def radix_bytes_per_mine(t: int, sizes, value_slots=None) -> int:
    """Stage 1 (one sort per mode) and Stage 3 (one 64-bit sort)."""
    stage1 = len(sizes) * radix_sort_bytes(t, key_bits(sizes, value_slots))
    return stage1 + radix_sort_bytes(t, 64)


def segment_reduce_bytes_per_mine(t: int, n_modes: int) -> int:
    """Stage 2: one fused three-lane prefix sweep per mode."""
    return n_modes * 6 * WORD * t
