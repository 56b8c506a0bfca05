"""Artifact integrity (ISSUE 10): checksum primitives, the build journal's
on-disk contract, and corruption detection for every artifact type —
truncation, bit-flips and torn writes must surface as a typed
``CorruptionError`` *naming the artifact*, never as a wrong answer.
"""
import json
import os

import numpy as np
import pytest

from repro.config import SAConfig
from repro.core import index_io
from repro.core.integrity import (
    CorruptionError,
    crc32_array,
    crc32_bytes,
    crc32_file,
    publish_dir,
    publish_file,
)
from repro.core.journal import BuildJournal, verify_spilled_run
from repro.core.oracle import naive_sa_reads
from repro.core.store import ChunkedFileBackend, InMemoryBackend
from repro.data.chunk_store import (
    ChunkedCorpusReader,
    write_chunked_corpus,
)

CFG = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)


def _corpus():
    rng = np.random.default_rng(3)
    return rng.integers(1, 5, size=(24, 8)).astype(np.int32)


def _flip_byte(path, offset):
    """Flip every bit of one byte; negative offsets count from the end."""
    with open(path, "r+b") as f:
        f.seek(offset, os.SEEK_END if offset < 0 else os.SEEK_SET)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))


def _truncate(path, drop_bytes):
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - drop_bytes)


# ---------------------------------------------------------------------------
# checksum + publish primitives
# ---------------------------------------------------------------------------


def test_crc_helpers_agree_across_views(tmp_path):
    arr = np.arange(100, dtype=np.int64).reshape(10, 10)
    assert crc32_array(arr) == crc32_bytes(arr.tobytes())
    # non-contiguous views hash their logical bytes, not their storage
    assert crc32_array(arr.T) == crc32_bytes(np.ascontiguousarray(arr.T).tobytes())
    p = tmp_path / "a.bin"
    p.write_bytes(arr.tobytes())
    assert crc32_file(str(p)) == crc32_array(arr)
    assert crc32_file(str(p), block=7) == crc32_array(arr)  # chunking-invariant


def test_publish_file_replaces_atomically(tmp_path):
    tmp, final = str(tmp_path / "x.tmp"), str(tmp_path / "x")
    with open(final, "w") as f:
        f.write("old")
    with open(tmp, "w") as f:
        f.write("new")
    publish_file(tmp, final)
    assert (tmp_path / "x").read_text() == "new"
    assert not os.path.exists(tmp)


def test_publish_dir_moves_tree(tmp_path):
    tmp, final = tmp_path / "d.tmp", tmp_path / "d"
    tmp.mkdir()
    (tmp / "f").write_text("payload")
    publish_dir(str(tmp), str(final))
    assert (final / "f").read_text() == "payload"
    assert not tmp.exists()


# ---------------------------------------------------------------------------
# chunk store: per-chunk footer checksums
# ---------------------------------------------------------------------------


def test_chunk_bitflip_names_the_chunk(tmp_path):
    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(_corpus(), path, chunk_items=8)
    _flip_byte(path, os.path.getsize(path) // 2)  # mid-payload
    with ChunkedCorpusReader(path) as r:
        with pytest.raises(CorruptionError, match=r"chunk \d+") as ei:
            for ci in range(r.meta.num_chunks):
                r.read_chunk(ci)  # salint: disable=SAL002
    assert ei.value.path == path


def test_verify_all_scans_every_chunk(tmp_path):
    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(_corpus(), path, chunk_items=8)
    with ChunkedCorpusReader(path) as r:
        assert r.verify_all() == r.meta.num_chunks
    _flip_byte(path, os.path.getsize(path) // 2)
    with ChunkedCorpusReader(path) as r:
        with pytest.raises(CorruptionError, match="chunk"):
            r.verify_all()


def test_checksum_table_truncation_detected(tmp_path):
    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(_corpus(), path, chunk_items=8)
    _truncate(path, 4)  # tear the footer's tail
    with pytest.raises(CorruptionError, match="chunk checksum table"):
        with ChunkedCorpusReader(path) as r:
            r.read_chunk(0)  # salint: disable=SAL002


def test_verify_off_reads_corrupt_bytes_unchecked(tmp_path):
    """verify=False is an explicit opt-out: corrupt payload bytes come back
    as data (the serving ``--verify off`` posture)."""
    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(_corpus(), path, chunk_items=8)
    _flip_byte(path, os.path.getsize(path) // 2)
    with ChunkedCorpusReader(path, verify=False) as r:
        for ci in range(r.meta.num_chunks):
            r.read_chunk(ci)  # no raise  # salint: disable=SAL002


def test_corrupt_chunk_is_never_retried(tmp_path):
    """End-to-end taxonomy check: a checksum failure inside the backend
    passes through the retry layer untouched."""
    from repro.core.store import RetryingBackend

    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(_corpus(), path, chunk_items=8)
    _flip_byte(path, os.path.getsize(path) // 2)
    backend = RetryingBackend(
        ChunkedFileBackend(path, CFG, cache_budget_bytes=1 << 12),
        retries=5, backoff_s=0.0, retryable=(Exception,))
    gidx = np.arange(_corpus().shape[0], dtype=np.int64) << backend.stride_bits
    with pytest.raises(CorruptionError):
        backend.gather(gidx, 0)  # salint: disable=SAL002
    assert backend.retry_attempts == 0
    backend.close()


def test_corrupt_chunk_after_resident_hits_is_not_cached(tmp_path):
    """A gather serves its resident chunks before it loads a missing one:
    a corrupt chunk loaded after those hits still raises, and is not
    cached."""
    from repro.data.chunk_store import HEADER_BYTES

    corpus = _corpus()  # 24 rows of 8: 4 chunks of 6 rows
    path = str(tmp_path / "c.sachunk")
    write_chunked_corpus(corpus, path, chunk_items=6)
    _flip_byte(path, HEADER_BYTES + 13 * 8 * 4)  # inside chunk 2
    ch = ChunkedFileBackend(path, CFG, cache_budget_bytes=4 * 6 * 8 * 4)

    def first_windows(*rows):
        gidx = np.array(rows, np.int64) << ch.stride_bits
        return ch.gather(gidx, 0)  # salint: disable=SAL002

    first_windows(18)  # chunk 3 resident
    resident, hits = ch.resident_bytes, ch.cache_hits
    for _ in range(2):  # nothing cached for it: the retry reads it again
        with pytest.raises(CorruptionError, match="chunk 2"):
            first_windows(12, 18)
        assert ch.resident_bytes == resident
    assert ch.cache_hits == hits + 2  # chunk 3 was served first each time
    assert ch.reordered_gathers == 2
    ch.close()


# ---------------------------------------------------------------------------
# build journal: crc'd records, torn-tail tolerance
# ---------------------------------------------------------------------------


def _write_journal(path, n_blocks=3):
    jr = BuildJournal(str(path)).open()
    jr.append({"t": "begin", "v": BuildJournal.VERSION, "fp": {"items": 24}})
    for i in range(n_blocks):
        jr.append({"t": "block", "i": i, "run": f"run_{i}.npy",
                   "run_crc": 7 + i, "rows": np.int64(10),
                   "stats": {"num_suffixes": np.int32(10)}, "fpc": {}})
    jr.close()


def test_journal_round_trips_numpy_scalars(tmp_path):
    p = tmp_path / "journal"
    _write_journal(p)
    records = BuildJournal.load(str(p))
    assert [r["t"] for r in records] == ["begin", "block", "block", "block"]
    # numpy scalars were coerced to natives at write; replay matches the crc
    assert records[1]["rows"] == 10
    assert records[1]["stats"]["num_suffixes"] == 10


def test_journal_torn_final_record_dropped_silently(tmp_path):
    p = tmp_path / "journal"
    _write_journal(p, n_blocks=2)
    with open(p, "r+b") as f:
        f.seek(0, os.SEEK_END)
        f.truncate(f.tell() - 11)  # tear into the last line (newline gone)
    records = BuildJournal.load(str(p))
    assert [r["t"] for r in records] == ["begin", "block"]  # last unit replays


def test_journal_interior_corruption_names_the_record(tmp_path):
    p = tmp_path / "journal"
    _write_journal(p, n_blocks=3)
    lines = p.read_bytes().split(b"\n")
    lines[2] = lines[2].replace(b'"run_1.npy"', b'"run_9.npy"')  # crc now wrong
    p.write_bytes(b"\n".join(lines))
    with pytest.raises(CorruptionError, match="build journal record 2"):
        BuildJournal.load(str(p))


def test_journal_garbage_interior_line_is_corruption(tmp_path):
    p = tmp_path / "journal"
    _write_journal(p, n_blocks=2)
    lines = p.read_bytes().split(b"\n")
    lines[1] = b"\x00\xff not json"
    p.write_bytes(b"\n".join(lines))
    with pytest.raises(CorruptionError, match="build journal record 1"):
        BuildJournal.load(str(p))


def test_spilled_run_verification(tmp_path):
    run = np.arange(50, dtype=np.int64)
    p = str(tmp_path / "run_0.npy")
    np.save(p, run)
    crc = crc32_array(run)
    mm = verify_spilled_run(p, crc, "spilled run run_0.npy")
    np.testing.assert_array_equal(mm, run)
    _flip_byte(p, -1)  # payload tail
    with pytest.raises(CorruptionError, match="spilled run run_0.npy"):
        verify_spilled_run(p, crc, "spilled run run_0.npy")
    _truncate(p, 30)  # now not even a loadable .npy
    with pytest.raises(CorruptionError, match="unreadable"):
        verify_spilled_run(p, crc, "spilled run run_0.npy")


# ---------------------------------------------------------------------------
# index artifacts: manifest digests + self-crc
# ---------------------------------------------------------------------------


@pytest.fixture()
def index_dir(tmp_path):
    corpus = _corpus()
    backend = InMemoryBackend(corpus, CFG)
    sa = naive_sa_reads(corpus).astype(np.int64)
    lcp = np.zeros(sa.shape[0], np.int32)
    index_io.save_index(str(tmp_path / "ix"), CFG, backend, sa, lcp=lcp)
    backend.close()
    return str(tmp_path / "ix")


def _close(opened):
    opened[0].close()


def test_open_index_verify_eager_passes_clean(index_dir):
    opened = index_io.open_index(index_dir, verify="eager")
    assert opened[3]["version"] == index_io.VERSION
    _close(opened)


@pytest.mark.parametrize("artifact", [index_io.SA_FILE, index_io.LCP_FILE])
def test_eager_open_names_flipped_array_artifact(index_dir, artifact):
    _flip_byte(os.path.join(index_dir, artifact), -1)
    with pytest.raises(CorruptionError, match=artifact):
        index_io.open_index(index_dir, verify="eager")


def test_eager_open_names_flipped_corpus(index_dir):
    path = os.path.join(index_dir, index_io.CORPUS_FILE)
    _flip_byte(path, os.path.getsize(path) // 2)
    with pytest.raises(CorruptionError, match=index_io.CORPUS_FILE):
        index_io.open_index(index_dir, verify="eager")


def test_lazy_open_defers_corpus_check_to_first_read(index_dir):
    path = os.path.join(index_dir, index_io.CORPUS_FILE)
    _flip_byte(path, os.path.getsize(path) // 2)
    backend, sa, lcp, manifest = index_io.open_index(index_dir, verify="lazy")
    try:
        with pytest.raises(CorruptionError, match="chunk"):
            # SA entries are global suffix indices: gathering them all pulls
            # every chunk through the (verifying) LRU load path
            backend.gather(np.asarray(sa), 0)  # salint: disable=SAL002
    finally:
        backend.close()


def test_verify_off_opens_flipped_index(index_dir):
    for artifact in (index_io.SA_FILE,):
        _flip_byte(os.path.join(index_dir, artifact), -1)
    backend, sa, lcp, manifest = index_io.open_index(index_dir, verify="off")
    assert sa.shape[0] > 0  # opens; the flipped bytes are the caller's risk
    backend.close()


def test_manifest_value_flip_fails_self_crc(index_dir):
    mpath = os.path.join(index_dir, index_io.MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["geometry"]["suffixes"] += 1  # parses fine; self-crc disagrees
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(CorruptionError, match="index manifest"):
        index_io.open_index(index_dir)


def test_manifest_truncation_is_corruption(index_dir):
    _truncate(os.path.join(index_dir, index_io.MANIFEST_NAME), 20)
    with pytest.raises(CorruptionError, match="index manifest"):
        index_io.open_index(index_dir)
