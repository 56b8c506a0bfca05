"""Crash-safe resumable builds (ISSUE 10): chaos kill/resume sweep,
transient-fault injection through the retrying store layer, and the shared
transient/fatal error taxonomy.

Acceptance properties:

* killing the out-of-core build at every announced ``pipeline_point`` and
  re-entering with ``resume=True`` yields a bit-identical SA (and LCP) on
  both store backends, sanitizer armed, with journaled blocks *not*
  rebuilt (``journal_hits`` asserted at kill sites past the spill drain,
  where every block record is durable by construction);
* deterministic transient faults injected into every build phase
  (``FlakyBackend``) are absorbed by ``RetryingBackend`` to a bit-identical
  SA with the gated ``FetchStats`` counters unchanged — retry accounting
  lives in its own (non-gated) counters;
* ``CorruptionError`` is never retried, neither by ``RetryingBackend`` nor
  by ``retry_step``, even under a blanket ``(Exception,)`` allowlist;
* the retry backoff sequence is deterministic and capped.
"""
import os

import numpy as np
import pytest

import repro.core.superblock as sbmod
from repro.config import SAConfig, SuperblockConfig
from repro.core.integrity import (
    CorruptionError,
    TransientError,
    TransientFault,
    retry_step,
)
from repro.core.journal import JOURNAL_NAME
from repro.core.store import (
    ChunkedFileBackend,
    FlakyBackend,
    InMemoryBackend,
    RetryingBackend,
)
from repro.core.superblock import build_suffix_array_superblock
from repro.data.chunk_store import write_chunked_corpus

CFG = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)

# every label the pipelined out-of-core build announces
PIPELINE_POINTS = (
    "spill:drain", "stage:collect", "build:block", "sink:append",
    "merge:refill", "merge:rank", "merge:collect", "merge:emit",
)
# at these points every block's journal record is already durable (the spill
# drain + forced journal flush precede the merge), so a resume may rebuild
# nothing at all
POST_DRAIN_POINTS = ("merge:refill", "merge:rank", "merge:collect",
                     "merge:emit", "sink:append")


def _corpus():
    rng = np.random.default_rng(7)
    return rng.integers(1, 5, size=(48, 12)).astype(np.int32)


def _sb(spill_dir, backend, **kw):
    kw.setdefault("sanitize", True)
    kw.setdefault("pipeline_depth", 1)
    return SuperblockConfig(
        num_superblocks=4, store_backend=backend, spill_dir=str(spill_dir),
        # corpus/2: tight enough that the residency assertion bites, big
        # enough that one block fits the staging-prefetch share (so the
        # "stage:collect" pipeline point is exercised too)
        resume=True, cache_budget_bytes=_corpus().size * 4 // 2,
        emit_lcp=True, **kw)


class _Kill(Exception):
    pass


def _run_with_kill(monkeypatch, corpus, sb, label, at):
    """Build, raising _Kill at the ``at``-th occurrence of ``label``.

    Patches the *superblock-module* binding: ``pipeline_point`` is imported
    by name into ``repro.core.superblock``, so patching pipeline_exec would
    not reach the build.
    """
    orig = sbmod.pipeline_point
    seen = {"n": 0}

    def probe(lbl):
        orig(lbl)
        if lbl == label:
            seen["n"] += 1
            if seen["n"] == at:
                raise _Kill(label)

    monkeypatch.setattr(sbmod, "pipeline_point", probe)
    try:
        with pytest.raises(_Kill):
            build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)
    finally:
        monkeypatch.setattr(sbmod, "pipeline_point", orig)


def _count_labels(monkeypatch, corpus, sb):
    """One journaled build, counting pipeline_point occurrences by label."""
    orig = sbmod.pipeline_point
    counts = {}

    def probe(lbl):
        orig(lbl)
        counts[lbl] = counts.get(lbl, 0) + 1

    monkeypatch.setattr(sbmod, "pipeline_point", probe)
    try:
        res = build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)
    finally:
        monkeypatch.setattr(sbmod, "pipeline_point", orig)
    return counts, res


@pytest.mark.parametrize("backend", ["memory", "chunked"])
def test_kill_and_resume_at_every_pipeline_point(monkeypatch, tmp_path,
                                                 backend):
    """The chaos sweep: for each pipeline point the backend reaches, kill
    the build at its *last* occurrence (maximum completed work at risk),
    then resume — the resumed SA/LCP must be bit-identical to an
    uninterrupted build, and post-drain kills must recover every block from
    the journal."""
    corpus = _corpus()
    counts, ref = _count_labels(monkeypatch, corpus,
                                _sb(tmp_path / "ref", backend))
    assert ref.stats["journaled"] and ref.stats["journal_hits"] == 0
    if backend == "chunked":
        # the streaming build must announce the full surface — a label the
        # sweep never kills at is a hole in the chaos coverage
        assert set(counts) == set(PIPELINE_POINTS), counts
    assert "build:block" in counts
    ref_sa = np.asarray(ref.suffix_array).copy()
    ref_lcp = np.asarray(ref.lcp).copy()

    for label in PIPELINE_POINTS:
        if label not in counts:
            continue
        d = tmp_path / label.replace(":", "_")
        sb = _sb(d, backend)
        _run_with_kill(monkeypatch, corpus, sb, label, at=counts[label])
        jpath = os.path.join(sb.spill_dir, JOURNAL_NAME)
        assert os.path.exists(jpath), f"{label}: no journal left to resume"
        res = build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)
        assert res.stats["journaled"]
        np.testing.assert_array_equal(
            np.asarray(res.suffix_array), ref_sa, err_msg=label)
        np.testing.assert_array_equal(
            np.asarray(res.lcp), ref_lcp, err_msg=label)
        if label in POST_DRAIN_POINTS:
            assert res.stats["journal_hits"] == res.stats["superblocks"], label
        if backend == "chunked":
            assert (res.footprint.peak_resident_bytes
                    <= sb.cache_budget_bytes), label
        # success retires the journal
        assert not os.path.exists(jpath), label


def test_resume_skips_completed_blocks(monkeypatch, tmp_path):
    """Killed after the spill drain: every block record is durable, and the
    resumed build rebuilds none of them."""
    corpus = _corpus()
    sb = _sb(tmp_path, "chunked")
    _run_with_kill(monkeypatch, corpus, sb, "merge:rank", at=1)
    res = build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)
    assert res.stats["journal_hits"] == res.stats["superblocks"] == 4
    ref = build_suffix_array_superblock(
        corpus, cfg=CFG,
        sb=SuperblockConfig(num_superblocks=4, sanitize=True))
    np.testing.assert_array_equal(res.suffix_array, ref.suffix_array)


def test_double_kill_then_resume(monkeypatch, tmp_path):
    """Two successive crashes at different points still resume to the exact
    SA — journal records accumulate monotonically across attempts."""
    corpus = _corpus()
    sb = _sb(tmp_path, "chunked")
    _run_with_kill(monkeypatch, corpus, sb, "build:block", at=2)
    _run_with_kill(monkeypatch, corpus, sb, "merge:emit", at=1)
    res = build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)
    assert res.stats["journal_hits"] == res.stats["superblocks"]
    ref = build_suffix_array_superblock(
        corpus, cfg=CFG, sb=SuperblockConfig(num_superblocks=4))
    np.testing.assert_array_equal(res.suffix_array, ref.suffix_array)


def test_resume_refuses_mismatched_fingerprint(monkeypatch, tmp_path):
    """A journal left by a different corpus/config must not be resumed
    against — silent cross-corpus resume would splice wrong runs."""
    corpus = _corpus()
    sb = _sb(tmp_path, "chunked")
    _run_with_kill(monkeypatch, corpus, sb, "merge:rank", at=1)
    other = corpus.copy()
    other[0, 0] = 3 if other[0, 0] != 3 else 2
    with pytest.raises(ValueError, match="fingerprint"):
        build_suffix_array_superblock(other, cfg=CFG, sb=sb)


def test_resume_detects_corrupt_spilled_run(monkeypatch, tmp_path):
    """A journaled run whose bytes no longer match the journaled crc is a
    CorruptionError naming the run — never a silent rebuild (the journal
    promised durability; the bytes disagree)."""
    from repro.core.journal import BuildJournal

    corpus = _corpus()
    sb = _sb(tmp_path, "chunked")
    _run_with_kill(monkeypatch, corpus, sb, "merge:rank", at=1)
    jpath = os.path.join(sb.spill_dir, JOURNAL_NAME)
    rec = next(r for r in BuildJournal.load(jpath) if r.get("t") == "block")
    run_path = os.path.join(sb.spill_dir, "scratch", rec["run"])
    with open(run_path, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CorruptionError, match="spilled run"):
        build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)


def test_resume_detects_corrupt_journal_record(monkeypatch, tmp_path):
    corpus = _corpus()
    sb = _sb(tmp_path, "chunked")
    _run_with_kill(monkeypatch, corpus, sb, "merge:rank", at=1)
    jpath = os.path.join(sb.spill_dir, JOURNAL_NAME)
    with open(jpath, "rb") as f:
        lines = f.read().split(b"\n")
    lines[1] = lines[1].replace(b'"t":"block"', b'"t":"clock"')
    with open(jpath, "wb") as f:
        f.write(b"\n".join(lines))
    with pytest.raises(CorruptionError, match="build journal record 1"):
        build_suffix_array_superblock(corpus, cfg=CFG, sb=sb)


def test_journaled_success_retires_journal_and_scratch(tmp_path):
    sb = _sb(tmp_path, "chunked")
    build_suffix_array_superblock(_corpus(), cfg=CFG, sb=sb)
    assert not os.path.exists(os.path.join(sb.spill_dir, JOURNAL_NAME))
    assert not os.path.exists(os.path.join(sb.spill_dir, "scratch"))


# ---------------------------------------------------------------------------
# transient-fault injection: retried to bit-identical output
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend_kind", ["memory", "chunked"])
def test_injected_faults_retried_to_identical_output(tmp_path, backend_kind):
    """FlakyBackend faults across every phase (staging reads + merge
    gathers), absorbed by RetryingBackend: bit-identical SA, gated
    FetchStats counters unchanged, retry accounting in its own counters."""
    corpus = _corpus()
    sb_base = dict(num_superblocks=4, sanitize=True,
                   cache_budget_bytes=1 << 14)

    def make_backend():
        if backend_kind == "memory":
            return InMemoryBackend(corpus, CFG)
        path = str(tmp_path / "c.sachunk")
        if not os.path.exists(path):
            write_chunked_corpus(corpus, path, chunk_items=8)
        return ChunkedFileBackend(path, CFG, cache_budget_bytes=1 << 13)

    clean_b = make_backend()
    clean = build_suffix_array_superblock(
        clean_b, cfg=CFG, sb=SuperblockConfig(**sb_base))
    clean_b.close()

    flaky = FlakyBackend(make_backend(), fail_every=3, failures_per_call=2)
    res = build_suffix_array_superblock(
        flaky, cfg=CFG,
        sb=SuperblockConfig(store_retries=3, store_backoff_s=0.0, **sb_base))
    flaky.close()

    assert flaky.injected > 0
    np.testing.assert_array_equal(res.suffix_array, clean.suffix_array)
    # the gated traffic counters are a property of the access schedule, not
    # of the medium's flakiness (SAL010 discipline: retries are accounted
    # separately, never folded into FetchStats)
    for key in ("merge_fetch_requests", "merge_fetch_bytes",
                "merge_fetch_rounds", "merge_retries"):
        assert res.stats[key] == clean.stats[key], key
    assert res.footprint.fetch_request == clean.footprint.fetch_request
    assert res.footprint.fetch_response == clean.footprint.fetch_response
    # retry accounting surfaces in its own counters
    assert res.stats["store_retry_attempts"] == flaky.injected
    assert res.stats["store_retried_calls"] > 0
    assert clean.stats["store_retry_attempts"] == 0


def test_faults_without_retry_layer_fail_fast():
    flaky = FlakyBackend(InMemoryBackend(_corpus(), CFG), fail_every=2)
    with pytest.raises(TransientError):
        build_suffix_array_superblock(
            flaky, cfg=CFG, sb=SuperblockConfig(num_superblocks=4))


def test_journaled_resume_composes_with_retry_layer(monkeypatch, tmp_path):
    """Kill a flaky-but-retried journaled build mid-merge, resume with the
    same flaky medium: still bit-identical."""
    corpus = _corpus()
    ref = build_suffix_array_superblock(
        corpus, cfg=CFG, sb=SuperblockConfig(num_superblocks=4))
    sb = _sb(tmp_path, "memory", store_retries=3, store_backoff_s=0.0)
    flaky = FlakyBackend(InMemoryBackend(corpus, CFG), fail_every=5,
                         failures_per_call=1)
    _run_with_kill(monkeypatch, flaky, sb, "merge:rank", at=1)
    res = build_suffix_array_superblock(flaky, cfg=CFG, sb=sb)
    flaky.close()
    assert res.stats["journal_hits"] == res.stats["superblocks"]
    np.testing.assert_array_equal(res.suffix_array, ref.suffix_array)


# ---------------------------------------------------------------------------
# RetryingBackend unit behavior
# ---------------------------------------------------------------------------


def test_retrying_backend_backoff_sequence_deterministic():
    inner = InMemoryBackend(_corpus(), CFG)
    flaky = FlakyBackend(inner, fail_reads={0}, failures_per_call=3)
    slept = []
    rb = RetryingBackend(flaky, retries=3, backoff_s=0.01, max_backoff_s=0.02,
                         sleep=slept.append)
    out = rb.read_items(0, 2)  # salint: disable=SAL002
    np.testing.assert_array_equal(
        out, inner.read_items(0, 2))  # salint: disable=SAL002
    assert slept == [0.01, 0.02, 0.02]  # doubled, then capped
    assert rb.retry_attempts == 3 and rb.retried_calls == 1
    assert rb.gave_up == 0


def test_retrying_backend_exhausts_budget():
    flaky = FlakyBackend(InMemoryBackend(_corpus(), CFG),
                         fail_reads={0}, failures_per_call=10)
    rb = RetryingBackend(flaky, retries=2, backoff_s=0.0)
    with pytest.raises(TransientError):
        rb.read_items(0, 2)  # salint: disable=SAL002
    assert rb.gave_up == 1 and rb.retry_attempts == 2


def test_retrying_backend_never_retries_corruption():
    class Corrupt(InMemoryBackend):
        calls = 0

        def read_items(self, lo, hi):
            type(self).calls += 1
            raise CorruptionError("chunk 0 of c.sachunk")

    rb = RetryingBackend(Corrupt(_corpus(), CFG), retries=5, backoff_s=0.0,
                         retryable=(Exception,))
    with pytest.raises(CorruptionError):
        rb.read_items(0, 2)  # salint: disable=SAL002
    assert Corrupt.calls == 1  # fatal on first sight, even under (Exception,)
    assert rb.retry_attempts == 0


# ---------------------------------------------------------------------------
# retry_step taxonomy (core.integrity)
# ---------------------------------------------------------------------------


def test_retry_step_default_preserves_blanket_behavior():
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient-ish")
        return "ok"

    assert retry_step(step, retries=3, backoff=0.0) == "ok"
    assert calls["n"] == 3


def test_retry_step_allowlist_narrows_retries():
    def bad():
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry_step(bad, retries=3, backoff=0.0, retryable=(TransientError,))

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise TransientFault("worker lost")
        return calls["n"]

    assert retry_step(flaky, retries=3, backoff=0.0,
                      retryable=(TransientError,)) == 2


def test_retry_step_never_retries_corruption():
    calls = {"n": 0}

    def poisoned():
        calls["n"] += 1
        raise CorruptionError("spilled run run_0.npy")

    with pytest.raises(CorruptionError):
        retry_step(poisoned, retries=5, backoff=0.0, retryable=(Exception,))
    assert calls["n"] == 1
