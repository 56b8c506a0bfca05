"""Configuration dataclasses of the suffix-array system.

:class:`SAConfig` configures the device pipeline and :class:`SuperblockConfig`
the out-of-core build around it.  All fields are plain python values so a
config serializes to json (the index manifest records the ``SAConfig``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional


# ---------------------------------------------------------------------------
# Suffix-array pipeline configuration (the paper's system)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SAConfig:
    """Configuration for distributed suffix-array construction.

    ``mode``:
      * ``"scheme"``   — the paper's scheme: index-only shuffle + on-demand
        window fetches from the in-memory store (paper §IV).
      * ``"terasort"`` — the paper's baseline: materialized padded suffixes
        shuffled in full (paper §III).
      * ``"doubling"`` — beyond-paper: prefix-doubling on ranks served from
        the same store abstraction (O(log n) rounds; for long texts).
    """

    mode: str = "scheme"
    vocab_size: int = 5  # $,A,C,G,T
    # tokens packed per 31-bit key word; 0 => derive max from vocab
    chars_per_word: int = 0
    key_words: int = 2
    packing: str = "base"  # base (paper-faithful) | bits (TPU-optimized)
    samples_per_shard: int = 256  # paper: 10000 per reducer
    # all_to_all bucket capacity = ceil(n_local) * slack
    shuffle_slack: float = 2.0
    # per-round fetch capacity as a fraction of local records (1.0 = all)
    fetch_fraction: float = 1.0
    max_rounds: int = 0  # 0 => derive from read length
    # paper's trick: suffixes shorter than the resolved prefix are final
    skip_exhausted: bool = True
    # server-side packing: respond with packed key words (8B) instead of raw
    # token windows (K bytes).  False = paper-faithful (raw suffix windows).
    server_pack: bool = True
    sort_group_threshold: int = 1 << 20  # paper: 1.6e6
    use_pallas: bool = False  # use Pallas kernels (interpreted on CPU)
    read_stride_bits: int = 0  # 0 => derive ceil(log2(L+1))
    # two-phase planning: run a cheap histogram pre-pass and size the shuffle
    # all_to_all capacity exactly (zero drops).  False = static heuristic
    # capacity (shuffle_slack), drops counted and drained where possible.
    adaptive: bool = True

    def resolved_chars_per_word(self) -> int:
        if self.chars_per_word:
            return self.chars_per_word
        if self.packing == "base":
            # max k with (vocab+1)^k < 2^31   (tokens shifted to 1..vocab, 0=$)
            k, cap = 0, 1
            while cap * (self.vocab_size + 1) < (1 << 31):
                cap *= self.vocab_size + 1
                k += 1
            return k
        bits = max(1, (self.vocab_size).bit_length())
        return max(1, 31 // bits)

    @property
    def prefix_len(self) -> int:
        return self.resolved_chars_per_word() * self.key_words


@dataclass(frozen=True)
class SuperblockConfig:
    """Out-of-core superblock construction (``repro.core.superblock``).

    A corpus whose suffix-record set exceeds what one ``shard_map`` run can
    hold is split into S *superblocks*.  Each superblock runs the ordinary
    pipeline (one run's records = one superblock's records), then the merge
    ranks all suffixes against sampled splitter suffixes with batched window
    fetches from the resident store — indexes move, tokens stay put — so no
    run ever materializes more than one superblock of 16-byte records.

    ``max_records_per_run``: capacity of a single pipeline run in suffix
      records.  0 = derive from ``num_superblocks`` (or stay in-core).
    ``num_superblocks``: explicit block-count override.  0 = derive from
      ``max_records_per_run``; both 0 = single-pass (in-core).
    ``samples_per_block``: splitter samples taken from each superblock's
      local SA (clamped so the pooled sample also fits one superblock).
    ``request_capacity``: merge-time store fetch batch size (requests per
      round; overflowing tie groups retry group-synchronously).
    ``merge_algorithm``: how the sorted block runs are merged.
      * ``"merge_path"`` (default) — batched merge-path tile merge: per
        tile, every run's next heads are fetched in one batched store call,
        packed to order-preserving key words, tie groups escalated together
        (batched deeper fetches, or one ``DeviceRefiner`` call on the
        device backend), and every candidate's output rank computed at once
        (``kernels/merge_path`` Pallas kernel under ``cfg.use_pallas``, the
        numpy ``CorpusStore.rank_windows`` reference otherwise).  No host
        heap walk — store round-trips collapse by the tile width (>= 5x
        fewer than ``kway``, asserted in ``tests/test_merge_path.py``).
      * ``"kway"`` — the PR-2 path: splitter ranks located inside each
        sorted run by O(log n) binary-search store comparisons, runs k-way
        merged at run heads through a host heap, windows fetched to
        tie-breaking depth (text mode re-ranks only the block-boundary risk
        set).  Kept as the round-trip reference.
      * ``"rerank"`` — the PR-1 baseline: every bucket is re-ranked from
        scratch by the group-synchronous refinement loop.  Kept as the
        merge-traffic reference (``tests/test_superblock.py``).
    ``merge_tile``: merge-path output-tile width (buffered run heads per
      run); 0 derives it — ``capacity_records // num_runs`` capped at 4096,
      or the frontier read-ahead budget in streaming builds.
    ``merge_backend``: where bucket refinement runs.
      * ``"host"`` (default) — numpy against the host-resident store.
      * ``"device"`` — the refinement loop runs TPU-resident under the same
        ``shard_map`` reducer as the pipeline, windows served by
        ``mget_window`` (``repro.core.pipeline.DeviceRefiner``).
    ``store_backend``: where the merge store's corpus bytes live.
      * ``"memory"`` (default) — host-resident array
        (``repro.core.store.InMemoryBackend``; out-of-*device* only).
      * ``"chunked"`` — chunked on-disk file + budgeted LRU chunk cache
        (``ChunkedFileBackend``): host-resident *corpus* bytes bounded by
        ``cache_budget_bytes``, so the corpus may exceed host RAM.  Block
        SAs are spilled to disk and the k-way merge runs with a bounded
        read-ahead frontier.  The final suffix array itself (8 B/suffix)
        is still returned as one host array — the remaining host ceiling
        (ROADMAP follow-up).  Requires ``merge_backend="host"`` (the
        device refiner needs the corpus HBM-resident).
    ``chunk_records``: corpus items (reads-mode rows / text tokens) per
      on-disk chunk when this build serializes the corpus itself; 0 derives
      ``repro.data.chunk_store.default_chunk_items`` (existing corpus files
      keep their own chunking).
    ``cache_budget_bytes``: resident-byte budget of the chunked backend's
      LRU chunk cache; the merge frontier read-ahead is sized from the same
      budget, and ``Footprint.peak_resident_bytes`` (cache + frontier) is
      bounded by it.  0 = 64 MiB default.
    ``spill_dir``: directory for the chunked build's scratch files (the
      serialized corpus when given an array, per-block SA spills); None = a
      private temporary directory, removed when the build finishes.  When
      set, the out-of-core build also **streams the output SA** there:
      merge pieces are emitted in final order straight into a preallocated
      ``{spill_dir}/suffix_array.npy`` disk memmap, which is returned as
      ``SAResult.suffix_array`` — no O(n) host output allocation.  The file
      outlives the build (scratch is still cleaned up).
    ``emit_lcp``: also produce the adjacent-pair LCP array (the query
      engine's O(m + log n) companion artifact, ``repro.core.lcp``).  The
      out-of-core merge computes it as pieces stream out (emit order is
      final order, so each pair costs one adjacent compare); a single-pass
      build names it on the device, in the refinement round that splits
      each pair, and fetches on the host only the pairs the device cannot
      name (``repro.core.lcp.finish_lcp``).  Streamed to ``{spill_dir}/lcp.npy``
      when spilling, host array otherwise.  Returned as ``SAResult.lcp``.
    ``write_manifest``: finalize ``spill_dir`` as a reopenable index
      directory (``repro.core.index_io``): ``manifest.json`` + the SA (+
      LCP) arrays + the serialized corpus (or a pointer to the caller's own
      corpus file).  Requires ``spill_dir``.  ``SuffixArrayIndex.open``
      serves such a directory with no rebuild.
    ``sanitize``: run the build under the runtime sanitizer
      (``repro.core.sanitize``): backend accounting cross-checked and a
      sampled window subset oracle-verified on every fetch, every emitted
      merge piece order-checked.  Equivalent to ``REPRO_SANITIZE=1``;
      output is bit-identical to an unsanitized build, only slower.
    ``pipeline_depth``: number of in-flight background buffers in the
      pipelined build (``repro.core.pipeline_exec``).  ``0`` runs the
      fully synchronous path; ``>= 1`` overlaps block staging with the
      device build, spill/output writes with the merge, and merge-tile
      key refills with tile ranking.  Output is bit-identical either
      way.  Staging prefetch additionally requires the prefetched block
      to fit inside ``cache_budget_bytes`` (prefetched bytes are counted
      against the budget via ``add_frontier``); when it does not fit,
      staging silently falls back to synchronous.
    ``resume``: arm the crash-safe build journal (requires ``spill_dir``):
      completed block runs are recorded (with content checksums) in an
      fsync'd append-only ``{spill_dir}/build.journal``, and re-entering
      the build with the same corpus/config replays it, skipping every
      verified-complete block — a killed build resumed this way produces a
      bit-identical suffix array without redoing finished work
      (``docs/fault_tolerance.md``).  On success the journal is retired.
    ``store_retries``: > 0 wraps the store backend in
      ``repro.core.store.RetryingBackend`` — transient fetch faults
      (``TransientError``) are retried up to this many times with capped
      exponential backoff before propagating; ``CorruptionError`` is never
      retried.  0 (default) = no wrapping.
    ``store_backoff_s``: base backoff delay for ``store_retries``
      (doubles per attempt, capped at 1 s).
    """

    max_records_per_run: int = 0
    num_superblocks: int = 0
    samples_per_block: int = 32
    request_capacity: int = 4096
    merge_algorithm: str = "merge_path"
    merge_tile: int = 0
    merge_backend: str = "host"
    store_backend: str = "memory"
    chunk_records: int = 0
    cache_budget_bytes: int = 0
    spill_dir: Optional[str] = None
    emit_lcp: bool = False
    write_manifest: bool = False
    sanitize: bool = False
    pipeline_depth: int = 1
    resume: bool = False
    store_retries: int = 0
    store_backoff_s: float = 0.01


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def asdict(cfg: Any) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


def replace(cfg: Any, **kw: Any) -> Any:
    return dataclasses.replace(cfg, **kw)
