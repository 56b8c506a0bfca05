"""Record / index types shared by the suffix-array pipelines.

A suffix is identified by a **global index** packed the way the paper packs
``sequence_number * 1000 + offset`` into a ``long`` — except we use a power of
two stride (shifts instead of division; documented adaptation in DESIGN.md §2)
and split the 62-bit quantity into two non-negative int31 words so the whole
record stays int32 (JAX x64 stays off, matching TPU-native dtypes).

Record layout (all int32, 16 bytes — identical width to the paper's long+long):

    [key_hi, key_lo, idx_hi, idx_lo]

``key_hi/key_lo`` hold the packed K-token prefix (order-preserving); sorting
lexicographically by (key_hi, key_lo, idx_hi, idx_lo) is exactly the paper's
reducer sort with stable index tie-breaking.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

# Sentinel key value: sorts after every real key (keys are < 2^31 - 1).
KEY_SENTINEL = np.int32(np.iinfo(np.int32).max)
# An LCP entry the device build could not name; the host finishes it.
LCP_UNNAMED = -1
# int31 word size used for index packing.
WORD_BITS = 31
WORD_MOD = 1 << WORD_BITS


def pack_index(read_id, offset, stride_bits: int):
    """(read_id, offset) -> (idx_hi, idx_lo) int32 words.

    gidx = read_id << stride_bits | offset, split into hi/lo int31 words.
    Works on numpy or jnp arrays.
    """
    xp = jnp if isinstance(read_id, jnp.ndarray) else np
    read_id = read_id.astype(xp.int64) if xp is np else read_id.astype(jnp.int32)
    if xp is np:
        gidx = (read_id.astype(np.int64) << stride_bits) | offset.astype(np.int64)
        return (
            (gidx >> WORD_BITS).astype(np.int32),
            (gidx & (WORD_MOD - 1)).astype(np.int32),
        )
    # jnp path: avoid int64 (x64 disabled).  read_id < 2^31; offset < 2^stride.
    # hi word = read_id >> (31 - stride_bits); lo = low bits of read_id
    # concatenated with offset.
    lo_bits = WORD_BITS - stride_bits
    hi = read_id >> lo_bits
    lo = ((read_id & ((1 << lo_bits) - 1)) << stride_bits) | offset
    return hi.astype(jnp.int32), lo.astype(jnp.int32)


def unpack_index(idx_hi, idx_lo, stride_bits: int):
    """(idx_hi, idx_lo) -> (read_id, offset).  numpy or jnp."""
    xp = jnp if isinstance(idx_hi, jnp.ndarray) else np
    lo_bits = WORD_BITS - stride_bits
    offset = idx_lo & ((1 << stride_bits) - 1)
    read_lo = idx_lo >> stride_bits
    read_id = (idx_hi << lo_bits) | read_lo
    return read_id.astype(xp.int32), offset.astype(xp.int32)


def global_index(idx_hi: np.ndarray, idx_lo: np.ndarray) -> np.ndarray:
    """Numpy only: combine words into one int64 global index."""
    return (idx_hi.astype(np.int64) << WORD_BITS) | idx_lo.astype(np.int64)


@dataclass
class Footprint:
    """Data-store footprint (paper §III): deterministic byte accounting.

    The paper's disk/HDFS/network categories map to HBM/ICI (DESIGN.md §2):

    * ``store_put``       — bytes of raw data resident in the in-memory store
                            (paper: Redis memory, incl. metadata overhead)
    * ``shuffle``         — bytes exchanged in the record all_to_all
                            (paper: MR shuffle)
    * ``fetch_request``   — bytes of index requests to the store
    * ``fetch_response``  — bytes of suffix windows returned (mgetsuffix)
    * ``materialized``    — peak bytes of suffix payloads materialized outside
                            the store (paper: map-side local write of suffixes)
    * ``output``          — bytes of the final SA
    """

    input: int = 0
    store_put: int = 0
    shuffle: int = 0
    fetch_request: int = 0
    fetch_response: int = 0
    materialized: int = 0
    output: int = 0
    rounds: int = 0
    dropped: int = 0
    # out-of-core accounting (core/superblock.py): number of superblocks the
    # build was split into, and the peak number of 16-byte suffix records any
    # single run (per-block pipeline, merge bucket, splitter batch) held at
    # once.  superblocks == 1 <=> single-pass in-core build.
    superblocks: int = 1
    peak_records: int = 0
    # store-layer residency (PR 3): peak bytes of the store working set —
    # backend chunk cache + merge frontier (cursor windows) — during an
    # out-of-core build.  With the chunked file backend this is bounded by
    # SuperblockConfig.cache_budget_bytes; 0 = not measured (single-pass).
    peak_resident_bytes: int = 0

    def total_traffic(self) -> int:
        return self.shuffle + self.fetch_request + self.fetch_response

    def units(self) -> dict:
        """Everything normalized to input size = 1 unit (paper's tables)."""
        ref = max(self.input, 1)
        return {
            "input": 1.0,
            "store_put": self.store_put / ref,
            "shuffle": self.shuffle / ref,
            "fetch_request": self.fetch_request / ref,
            "fetch_response": self.fetch_response / ref,
            "materialized": self.materialized / ref,
            "output": self.output / ref,
            "rounds": self.rounds,
            "dropped": self.dropped,
            "superblocks": self.superblocks,
            "peak_record_bytes": self.peak_records * 16 / ref,
            "peak_resident": self.peak_resident_bytes / ref,
        }


@dataclass
class SAResult:
    """Result of a suffix-array build."""

    # (n,) int64 global suffix indexes in sorted suffix order (numpy, host)
    suffix_array: np.ndarray
    footprint: Footprint
    stats: dict
    # (n,) int64 adjacent-pair LCP array (lcp[i] = LCP(sa[i-1], sa[i]),
    # lcp[0] = 0) when the build was asked for it (SuperblockConfig.emit_lcp
    # / repro.core.lcp); None otherwise.  From build_suffix_array(lcp=True)
    # the pairs the device could not name hold LCP_UNNAMED until
    # repro.core.lcp.finish_lcp fills them.
    lcp: Optional[np.ndarray] = None

    def read_offset(self, stride_bits: int) -> Tuple[np.ndarray, np.ndarray]:
        sa = self.suffix_array
        return (sa >> stride_bits).astype(np.int64), (
            sa & ((1 << stride_bits) - 1)
        ).astype(np.int64)
