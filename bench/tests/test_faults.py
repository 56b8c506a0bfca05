"""Whole runs of the small cells on CPU, with the timed path broken
underneath: each fault has to turn ``correct`` false, and the sound path
has to stay correct."""
import numpy as np
import pytest

from repro.serve import sa_engine

BUILD_CELLS = ["grouper-reads.build", "grouper-reads-ooc.build"]
LOOKUP = "grouper-seed-index.lookup"


def _stale_index(monkeypatch):
    """Every build publishes the index of the first read set it saw: the
    state never changes from job to job."""
    build = sa_engine.SuffixArrayIndex.build.__func__
    first = []

    def stale(cls, corpus, *a, **kw):
        first.append(np.asarray(corpus))
        return build(cls, first[0], *a, **kw)

    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "build",
                        classmethod(stale))


def _half_reads(monkeypatch):
    """Every build indexes the first half of its reads only."""
    build = sa_engine.SuffixArrayIndex.build.__func__

    def half(cls, corpus, *a, **kw):
        corpus = np.asarray(corpus)
        return build(cls, corpus[: corpus.shape[0] // 2], *a, **kw)

    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "build",
                        classmethod(half))


def _token_altered(monkeypatch):
    """Every build indexes its reads with one token changed."""
    build = sa_engine.SuffixArrayIndex.build.__func__

    def altered(cls, corpus, *a, **kw):
        corpus = np.array(corpus)
        corpus[corpus.shape[0] // 2, 3] = corpus[corpus.shape[0] // 2, 3] % 4 + 1
        return build(cls, corpus, *a, **kw)

    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "build",
                        classmethod(altered))


def _stale_answers(monkeypatch):
    """count and locate return the first answers they gave, whatever the
    batch."""
    for name in ("count", "locate"):
        orig = getattr(sa_engine.SuffixArrayIndex, name)
        seen = {}

        def stale(self, patterns, _orig=orig, _seen=seen):
            if "out" not in _seen:
                _seen["out"] = _orig(self, patterns)
            return _seen["out"]

        monkeypatch.setattr(sa_engine.SuffixArrayIndex, name, stale)


def _half_batch(monkeypatch):
    """Only the first half of each batch is answered; the rest read as
    absent."""
    count = sa_engine.SuffixArrayIndex.count
    locate = sa_engine.SuffixArrayIndex.locate

    def half_count(self, patterns):
        out = np.zeros(len(patterns), np.int64)
        out[: len(patterns) // 2] = count(self, patterns[: len(patterns) // 2])
        return out

    def half_locate(self, patterns):
        out = locate(self, patterns[: len(patterns) // 2])
        return list(out) + [np.zeros(0, np.int64)] * (len(patterns) - len(out))

    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "count", half_count)
    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "locate", half_locate)


def _answer_altered(monkeypatch):
    """One count of each batch is off by one."""
    count = sa_engine.SuffixArrayIndex.count

    def altered(self, patterns):
        out = np.array(count(self, patterns))
        out[0] += 1
        return out

    monkeypatch.setattr(sa_engine.SuffixArrayIndex, "count", altered)


@pytest.mark.parametrize("cell", BUILD_CELLS + [LOOKUP])
def test_sound_run_is_correct(cpu_run, cell):
    res = cpu_run(cell)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("fault", [_stale_index, _half_reads, _token_altered])
@pytest.mark.parametrize("cell", BUILD_CELLS)
def test_build_fault_is_not_correct(cpu_run, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = cpu_run(cell)
    assert not res["correct"], res
    assert res["failed"] > 0


@pytest.mark.parametrize("fault", [_stale_answers, _half_batch,
                                   _answer_altered])
def test_lookup_fault_is_not_correct(cpu_run, monkeypatch, fault):
    fault(monkeypatch)
    res = cpu_run(LOOKUP)
    assert not res["correct"], res
    assert res["failed"] > 0
