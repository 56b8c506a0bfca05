"""Merge-path tile merge (ISSUE 5): oracle identity, round-trip collapse,
the shared packed-key compare path, and the streamed-output satellites.

Acceptance properties:

* ``merge_algorithm="merge_path"`` is **oracle-identical** to ``kway`` and
  ``rerank`` on both store backends (in-memory + chunked), reads + text,
  >= 3 superblocks — hypothesis-swept plus the repetitive-text deep-tie
  degenerate case;
* the merge makes **>= 5x fewer store round-trips** than the k-way heap walk
  at equal config (round-trips, not bytes: bytes stay comparable, the calls
  collapse by the tile width);
* the ``kernels/merge_path`` Pallas kernel matches ``ref.merge_path_ranks_ref``
  and the numpy comparator ``CorpusStore.rank_windows`` (one compare path);
* ``pack_keys_np`` mirrors ``encoding.pack_words`` bit-exactly;
* the output SA streams into a ``spill_dir`` memmap; ``write_chunked_stream``
  serializes a generator identically to the one-shot writer.
"""
import os

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.config import SAConfig, SuperblockConfig
from repro.core import encoding
from repro.core.oracle import doubling_sa_text, naive_sa_reads, naive_sa_text
from repro.core.store import ChunkedFileBackend, CorpusStore, pack_keys_np
from repro.core.superblock import build_suffix_array_superblock
from repro.data.chunk_store import (
    ChunkedCorpusReader,
    write_chunked_corpus,
    write_chunked_stream,
)

CFG = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)  # K=4: forces rounds


def _build(corpus, alg, s=3, **kw):
    sb = SuperblockConfig(num_superblocks=s, merge_algorithm=alg, **kw)
    return build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)


# ---------------------------------------------------------------------------
# oracle identity across algorithms and backends
# ---------------------------------------------------------------------------


@given(r=st.integers(12, 40), l=st.integers(4, 12), seed=st.integers(0, 10_000))
@settings(max_examples=5, deadline=None)
def test_property_merge_path_oracle_identical_reads(r, l, seed):
    rng = np.random.default_rng(seed)
    reads = rng.integers(1, 5, size=(r, l)).astype(np.int32)
    mp = _build(reads, "merge_path")
    np.testing.assert_array_equal(mp.suffix_array, naive_sa_reads(reads))
    for alg in ("kway", "rerank"):
        np.testing.assert_array_equal(
            mp.suffix_array, _build(reads, alg).suffix_array)


@given(n=st.integers(60, 300), seed=st.integers(0, 10_000))
@settings(max_examples=5, deadline=None)
def test_property_merge_path_oracle_identical_text(n, seed):
    rng = np.random.default_rng(seed)
    text = rng.integers(1, 5, size=(n,)).astype(np.int32)
    mp = _build(text, "merge_path")
    np.testing.assert_array_equal(mp.suffix_array, doubling_sa_text(text))
    for alg in ("kway", "rerank"):
        np.testing.assert_array_equal(
            mp.suffix_array, _build(text, alg).suffix_array)


@pytest.mark.parametrize("verify", [None, True, False],
                         ids=["array", "file-verified", "file-unverified"])
def test_merge_path_chunked_backend_matches_memory(tmp_path, verify):
    """Both store backends, reads + text: identical SA and the streaming
    residency bound still held by the tile frontier accounting.  The
    chunked build reads either the caller's array (serialized by the
    build) or a corpus file opened with and without per-chunk crc checks:
    checksumming is a pure observer, so all three give the oracle's SA."""
    rng = np.random.default_rng(7)
    reads = rng.integers(1, 5, size=(128, 16)).astype(np.int32)
    text = rng.integers(1, 5, size=(768,)).astype(np.int32)
    for name, corpus, oracle in (("reads", reads, naive_sa_reads(reads)),
                                 ("text", text, doubling_sa_text(text))):
        budget = corpus.size * 4 // 4
        mem = _build(corpus, "merge_path", s=4)
        if verify is None:
            ch = _build(corpus, "merge_path", s=4, store_backend="chunked",
                        cache_budget_bytes=budget)
        else:
            path = str(tmp_path / f"{name}.sachunk")
            # chunks of 512 B (reads) / 256 B (text): the LRU's half of the
            # budget holds a few
            write_chunked_corpus(corpus, path,
                                 chunk_items=8 if corpus.ndim == 2 else 64)
            backend = ChunkedFileBackend(path, CFG,
                                         cache_budget_bytes=budget // 2,
                                         verify=verify)
            try:
                ch = _build(backend, "merge_path", s=4,
                            store_backend="chunked", cache_budget_bytes=budget)
            finally:
                backend.close()
        np.testing.assert_array_equal(mem.suffix_array, oracle)
        np.testing.assert_array_equal(ch.suffix_array, oracle)
        assert 0 < ch.footprint.peak_resident_bytes <= budget


def test_merge_path_repetitive_text_degenerate():
    """ATAT... text: every comparison is a deep tie resolved only at the
    text end — nearly all suffixes are boundary-risk, the re-ranked pieces
    bypass the tile merge, and the result must stay oracle-exact."""
    text = np.tile(np.array([1, 2], np.int32), 180)
    mp = _build(text, "merge_path")
    np.testing.assert_array_equal(mp.suffix_array, naive_sa_text(text))
    np.testing.assert_array_equal(
        mp.suffix_array, _build(text, "kway").suffix_array)
    # chunked backend on the degenerate case: correctness only (the frontier
    # floor is documented in docs/out_of_core.md)
    ch = _build(text, "merge_path", store_backend="chunked",
                cache_budget_bytes=text.size * 4 * 4)
    np.testing.assert_array_equal(ch.suffix_array, naive_sa_text(text))


def test_merge_path_repetitive_reads():
    """Identical ATAT reads: deep cross-run ties in every tile, escalated
    group-wise to the read end and broken by index."""
    reads = np.tile(np.array([1, 2] * 6, np.int32), (36, 1))
    mp = _build(reads, "merge_path")
    np.testing.assert_array_equal(mp.suffix_array, naive_sa_reads(reads))


def test_merge_path_variable_length_reads():
    rng = np.random.default_rng(1)
    lens = rng.integers(0, 11, size=(30,)).astype(np.int32)
    reads = np.zeros((30, 11), np.int32)
    for i, n in enumerate(lens):
        reads[i, :n] = rng.integers(1, 5, size=(n,))
    res = build_suffix_array_superblock(
        reads, lengths=lens, cfg=CFG,
        sb=SuperblockConfig(num_superblocks=3, merge_algorithm="merge_path"))
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads, lens))


def test_merge_path_device_backend_reads():
    """merge_backend="device": tie groups are escalated by one DeviceRefiner
    call per tile instead of host depth fetches."""
    rng = np.random.default_rng(5)
    for corpus in (rng.integers(1, 5, size=(48, 12)).astype(np.int32),
                   np.tile(np.array([1, 2] * 6, np.int32), (36, 1))):
        res = _build(corpus, "merge_path", merge_backend="device")
        np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(corpus))


def test_merge_path_with_pallas_kernel():
    """cfg.use_pallas routes the tile ranking through the Pallas kernel."""
    cfg = SAConfig(vocab_size=4, chars_per_word=2, key_words=2,
                   use_pallas=True)
    rng = np.random.default_rng(11)
    reads = rng.integers(1, 5, size=(30, 11)).astype(np.int32)
    res = build_suffix_array_superblock(
        reads, cfg=cfg, sb=SuperblockConfig(num_superblocks=3))
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))


def test_merge_path_tiny_tile_still_exact():
    """merge_tile=2 forces many tiles and maximal refill churn; the safety
    horizon must still emit every suffix exactly once, in order."""
    rng = np.random.default_rng(21)
    reads = rng.integers(1, 5, size=(48, 12)).astype(np.int32)
    res = _build(reads, "merge_path", merge_tile=2)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))


# ---------------------------------------------------------------------------
# the >= 5x round-trip collapse (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------


def _roundtrips(corpus, alg, s):
    res = _build(corpus, alg, s=s)
    return res, res.stats["merge_fetch_rounds"]


@pytest.mark.parametrize("shape", [(48, 12), (576,)], ids=["reads", "text"])
def test_merge_path_roundtrips_beat_kway_5x_random(shape):
    rng = np.random.default_rng(0)
    corpus = rng.integers(1, 5, size=shape).astype(np.int32)
    mp, r_mp = _roundtrips(corpus, "merge_path", 4)
    kw_, r_kw = _roundtrips(corpus, "kway", 4)
    np.testing.assert_array_equal(mp.suffix_array, kw_.suffix_array)
    assert r_kw >= 5 * r_mp, (r_mp, r_kw)
    # bytes stay comparable (the win is calls, not payload): within 2x
    assert mp.stats["merge_fetch_bytes"] <= 2 * kw_.stats["merge_fetch_bytes"]


def test_merge_path_roundtrips_beat_kway_5x_repetitive():
    """The heap walk's worst case: every tie deepens through singleton
    fetch rounds; the tile merge escalates whole groups per round."""
    reads = np.tile(np.array([1, 2] * 6, np.int32), (36, 1))
    mp, r_mp = _roundtrips(reads, "merge_path", 3)
    kw_, r_kw = _roundtrips(reads, "kway", 3)
    np.testing.assert_array_equal(mp.suffix_array, kw_.suffix_array)
    assert r_kw >= 5 * r_mp, (r_mp, r_kw)


# ---------------------------------------------------------------------------
# the shared compare path: pack_keys_np / rank_windows / the kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    SAConfig(vocab_size=4, packing="base"),
    SAConfig(vocab_size=4, packing="bits"),
    SAConfig(vocab_size=4, chars_per_word=3, key_words=2, packing="base"),
    SAConfig(vocab_size=255, packing="bits"),
], ids=lambda c: f"{c.packing}-v{c.vocab_size}")
def test_pack_keys_np_matches_encoding(cfg):
    """The numpy packer is bit-identical to the canonical jnp pack_words
    (including end-of-suffix zero padding inside a window)."""
    rng = np.random.default_rng(3)
    win = rng.integers(0, cfg.vocab_size + 1,
                       size=(64, cfg.prefix_len)).astype(np.int32)
    want = np.asarray(encoding.pack_words(jnp.asarray(win), cfg))
    np.testing.assert_array_equal(pack_keys_np(win, cfg), want)


def test_rank_windows_is_the_merge_permutation():
    """rank_windows == lexicographic (keys..., gidx) argsort rank — the host
    reference of the merge-path kernel."""
    rng = np.random.default_rng(4)
    store = CorpusStore(np.ones(16, np.int32), CFG)
    keys = rng.integers(0, 5, size=(40, 3)).astype(np.int32)  # many ties
    gidx = rng.permutation(40).astype(np.int64)
    ranks = store.rank_windows(keys, gidx)
    assert sorted(ranks.tolist()) == list(range(40))
    rows = [tuple(keys[i]) + (gidx[i],) for i in range(40)]
    by_rank = np.argsort(ranks)
    assert [rows[i] for i in by_rank] == sorted(rows)


def test_kernel_matches_rank_windows():
    """Pallas kernel (interpret), jnp ref, and the numpy comparator agree on
    the same tile — the three implementations of one compare path."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(5)
    store = CorpusStore(np.ones(16, np.int32), CFG)
    words = rng.integers(0, 4, size=(70, 2)).astype(np.int32)  # heavy ties
    gidx = rng.permutation(70).astype(np.int64)
    host = store.rank_windows(words, gidx)
    keys_full = np.concatenate(
        [words,
         (gidx >> 31).astype(np.int32)[:, None],
         (gidx & ((1 << 31) - 1)).astype(np.int32)[:, None]], axis=1)
    kern = np.asarray(ops.merge_path_ranks(jnp.asarray(keys_full), block=32))
    refr = np.asarray(ref.merge_path_ranks_ref(jnp.asarray(keys_full)))
    np.testing.assert_array_equal(kern, refr)
    np.testing.assert_array_equal(kern, host)


# ---------------------------------------------------------------------------
# streamed output SA (spill_dir memmap satellite)
# ---------------------------------------------------------------------------


def test_output_sa_streams_to_memmap(tmp_path):
    rng = np.random.default_rng(9)
    reads = rng.integers(1, 5, size=(96, 12)).astype(np.int32)
    res = build_suffix_array_superblock(reads, cfg=CFG, sb=SuperblockConfig(
        num_superblocks=3, store_backend="chunked",
        spill_dir=str(tmp_path)))
    assert isinstance(res.suffix_array, np.memmap)
    assert res.suffix_array.filename == str(tmp_path / "suffix_array.npy")
    np.testing.assert_array_equal(np.asarray(res.suffix_array),
                                  naive_sa_reads(reads))
    # without a spill_dir the result is an ordinary host array
    plain = _build(reads, "merge_path")
    assert not isinstance(plain.suffix_array, np.memmap)
    np.testing.assert_array_equal(plain.suffix_array,
                                  np.asarray(res.suffix_array))


def test_output_memmap_survives_spill_dir_reuse(tmp_path):
    """A second build into the same spill_dir must not truncate the inode a
    previous build's returned memmap still maps (the sink writes to a temp
    name and renames atomically on completion)."""
    rng = np.random.default_rng(10)
    a = rng.integers(1, 5, size=(48, 12)).astype(np.int32)
    b = rng.integers(1, 5, size=(48, 12)).astype(np.int32)
    sb = SuperblockConfig(num_superblocks=3, store_backend="chunked",
                          spill_dir=str(tmp_path))
    res_a = build_suffix_array_superblock(a, cfg=CFG, sb=sb)
    snap_a = np.asarray(res_a.suffix_array).copy()
    res_b = build_suffix_array_superblock(b, cfg=CFG, sb=sb)
    np.testing.assert_array_equal(np.asarray(res_a.suffix_array), snap_a)
    np.testing.assert_array_equal(np.asarray(res_b.suffix_array),
                                  naive_sa_reads(b))
    # the published name now holds build B; no temp litter remains
    np.testing.assert_array_equal(
        np.load(str(tmp_path / "suffix_array.npy")), res_b.suffix_array)
    assert os.listdir(str(tmp_path)) == ["suffix_array.npy"]


# ---------------------------------------------------------------------------
# streaming corpus writer (write_chunked_stream satellite)
# ---------------------------------------------------------------------------


def _batches(arr, sizes):
    lo = 0
    for s in sizes:
        yield arr[lo : lo + s]
        lo += s
    if lo < arr.shape[0]:
        yield arr[lo:]


def test_write_chunked_stream_matches_oneshot_reads(tmp_path):
    rng = np.random.default_rng(12)
    reads = rng.integers(1, 5, size=(37, 9)).astype(np.int32)
    p1 = str(tmp_path / "oneshot.sachunk")
    p2 = str(tmp_path / "stream.sachunk")
    write_chunked_corpus(reads, p1, chunk_items=5)
    meta = write_chunked_stream(_batches(reads, [1, 7, 3, 11]), p2,
                                chunk_items=5)
    assert meta.items == 37 and meta.row_len == 9 and not meta.text_mode
    with open(p1, "rb") as a, open(p2, "rb") as b:
        assert a.read() == b.read()  # byte-identical file (header included)
    with ChunkedCorpusReader(p2) as r:
        # raw read on purpose: this asserts the on-disk format itself
        np.testing.assert_array_equal(
            r.read_items(0, 37), reads)  # salint: disable=SAL002


def test_write_chunked_stream_matches_oneshot_text(tmp_path):
    rng = np.random.default_rng(13)
    text = rng.integers(1, 5, size=(101,)).astype(np.int32)
    p1 = str(tmp_path / "oneshot.sachunk")
    p2 = str(tmp_path / "stream.sachunk")
    write_chunked_corpus(text, p1, chunk_items=16)
    write_chunked_stream(_batches(text, [50, 1, 20]), p2, chunk_items=16)
    with open(p1, "rb") as a, open(p2, "rb") as b:
        assert a.read() == b.read()


def test_write_chunked_stream_rejects_bad_input(tmp_path):
    p = str(tmp_path / "x.sachunk")
    with pytest.raises(ValueError, match="empty batch iterable"):
        write_chunked_stream(iter([]), p)
    reads = np.ones((4, 6), np.int32)
    with pytest.raises(ValueError, match="does not match"):
        write_chunked_stream(iter([reads, np.ones((2, 5), np.int32)]), p)
    # a failed stream must not leave a valid-looking items=0 file behind
    assert not os.path.exists(p)
    # the public facade exports the writer alongside its one-shot sibling
    from repro.data import write_chunked_stream as facade_writer
    assert facade_writer is write_chunked_stream


def test_write_chunked_stream_feeds_superblock_build(tmp_path):
    """The generator-serialized file is a first-class corpus argument."""
    rng = np.random.default_rng(14)
    reads = rng.integers(1, 5, size=(96, 12)).astype(np.int32)
    p = str(tmp_path / "gen.sachunk")
    write_chunked_stream(_batches(reads, [30, 30, 30]), p, chunk_items=8)
    res = build_suffix_array_superblock(p, cfg=CFG, sb=SuperblockConfig(
        num_superblocks=3, cache_budget_bytes=reads.size))
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))
    assert os.path.exists(p)  # the corpus file is kept for reuse
