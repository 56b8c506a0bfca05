"""Spans and counters inside the program.

* The host spans land in a profiler trace, nested as the layers call each
  other: ``build.lcp`` and ``index.save`` inside a build, and a chunk-cache
  miss (``store.chunk_read``) inside a window fetch (``store.fetch``)
  inside a serve round (``serve.round``).
* ``engine_stats()`` counts the corpus chunk cache's hits, misses and
  evictions exactly.
* The build stats carry every phase timer, the merge's phases inside the
  merge.
* The device pipeline's phases are named scopes in its compiled HLO.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.config import SAConfig, SuperblockConfig
from repro.core import pipeline
from repro.data.chunk_store import read_chunked_corpus_meta
from repro.serve.sa_engine import SuffixArrayIndex

CFG = SAConfig(vocab_size=4, packing="base")
READS, LEN = 64, 30


def _reads(seed=0):
    return np.random.default_rng(seed).integers(
        1, 5, size=(READS, LEN)).astype(np.int32)


def _host_events(trace_dir):
    """``{thread: [(name, start_ns, end_ns), ...]}`` of the trace's host
    plane."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out[line.name] = [(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events]
    return out


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_spans_nest_in_profiler_trace(tmp_path):
    reads = _reads()
    index_dir = str(tmp_path / "index")
    tdir = str(tmp_path / "trace")
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation("test.build"):
            SuffixArrayIndex.build(reads, cfg=CFG, index_dir=index_dir).close()
        with SuffixArrayIndex.open(index_dir, store_backend="chunked",
                                   cache_budget_bytes=1 << 20) as idx:
            assert (idx.count(reads[:8, 3:12]) >= 1).all()
    finally:
        jax.profiler.stop_trace()
    threads = _host_events(tdir)
    (main,) = [ev for ev in threads.values() if _named(ev, "test.build")]
    (build,) = _named(main, "test.build")
    (lcp,) = _named(main, "build.lcp")
    (save,) = _named(main, "index.save")
    assert _inside(lcp, build) and _inside(save, build)
    assert lcp[2] <= save[1]  # the LCP is published, not computed, in save
    # the device named every pair of this one-device build of uniform
    # reads, so the LCP pass has no pair to fetch windows for
    assert not any(_inside(f, lcp) for f in _named(main, "store.fetch"))

    reads_ = _named(main, "store.chunk_read")
    assert reads_  # a fresh chunk cache misses
    fetches, rounds = _named(main, "store.fetch"), _named(main, "serve.round")
    for r in reads_:
        f = [f for f in fetches if _inside(r, f)]
        assert f and any(_inside(f[0], rd) for rd in rounds), r
    verifies = _named(main, "store.chunk_verify")  # the lazy crc check
    assert verifies and all(any(_inside(v, r) for r in reads_)
                            for v in verifies)


@pytest.fixture(scope="module")
def chunked_index(tmp_path_factory):
    """An index of 64 reads whose corpus file holds C = 8 chunks."""
    index_dir = str(tmp_path_factory.mktemp("chunks") / "index")
    SuffixArrayIndex.build(_reads(1), cfg=CFG, index_dir=index_dir,
                           sb=SuperblockConfig(chunk_records=8)).close()
    meta = read_chunked_corpus_meta(os.path.join(index_dir,
                                                 "corpus.sachunk"))
    assert meta.num_chunks == 8
    return index_dir, meta


@pytest.mark.parametrize("cached,misses,hits,evictions", [
    # C - 1 chunks: the second scan serves the 7 resident chunks first,
    # then loads chunk 0 alone (an ascending scan would miss all 8)
    (7, 9, 7, 2),
    (8, 8, 8, 0),  # C chunks: the second scan hits every chunk
])
def test_chunk_counters_exact(chunked_index, cached, misses, hits,
                              evictions):
    index_dir, meta = chunked_index
    with SuffixArrayIndex.open(index_dir, store_backend="chunked",
                               cache_budget_bytes=cached * meta.chunk_bytes
                               ) as idx:
        store, engine = idx.store, idx.engine
        first = np.arange(READS, dtype=np.int64) << store.stride_bits
        for _ in range(2):  # every read's first window, ascending
            store.fetch_windows(first, 0)
        st = engine.engine_stats()
    assert (st["chunk_misses"], st["chunk_hits"],
            st["chunk_evictions"]) == (misses, hits, evictions)
    assert st["t_chunk_read_s"] > 0 and st["t_fetch_s"] >= \
        st["t_chunk_read_s"]
    assert st["cache_hits"] == st["cache_misses"] == 0  # the result cache


def test_chunk_counters_zero_in_memory(chunked_index):
    index_dir, _ = chunked_index
    with SuffixArrayIndex.open(index_dir, store_backend="memory") as idx:
        idx.count(_reads(1)[:4, :10])
        st = idx.engine.engine_stats()
    assert st["chunk_hits"] == st["chunk_misses"] == \
        st["chunk_evictions"] == 0
    assert st["t_chunk_read_s"] == 0.0
    assert st["compare_rounds"] > 0
    assert st["compare_rows"] >= st["compare_rounds"]
    assert st["t_compare_s"] > 0


PUBLISH = {"t_device_s", "t_corpus_s", "t_save_s", "t_open_s"}
MERGE = {"t_merge_refill_s", "t_merge_escalate_s", "t_merge_rank_s",
         "t_merge_collect_s", "t_merge_emit_s"}


@pytest.mark.parametrize("superblocks", [1, 4])
def test_build_stats_phase_timers(tmp_path, superblocks):
    per_run = READS // superblocks * (LEN + 1)  # one run: the whole corpus
    sb = SuperblockConfig(store_backend="chunked",
                          max_records_per_run=per_run)
    with SuffixArrayIndex.build(_reads(2), cfg=CFG, sb=sb,
                                index_dir=str(tmp_path / "index")) as idx:
        st = idx.build_stats
    timers = {k: v for k, v in st.items() if re.fullmatch(r"t_\w+_s", k)}
    assert all(v >= 0 for v in timers.values())
    if superblocks == 1:
        assert set(timers) == PUBLISH | {"t_lcp_s"}
        return
    assert st["superblocks"] == superblocks
    # the phases the out-of-core build had timed keep their keys
    assert set(timers) == (PUBLISH | MERGE
                           | {"t_stage_s", "t_build_s", "t_merge_s",
                              "t_fetch_s"})
    assert sum(st[k] for k in MERGE) <= st["t_merge_s"]
    assert 0 < st["t_fetch_s"] <= st["t_merge_s"]  # the merge's fetches
    assert st["t_device_s"] <= st["t_build_s"]
    assert st["t_merge_collect_s"] > 0  # the refill prefetch ran


def _op_names(compiled) -> str:
    return "\n".join(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def test_pipeline_phases_are_named_scopes():
    mesh = Mesh(np.array(jax.devices()[:1]), (pipeline.AXIS,))
    shape = (16, LEN)
    fn, info = pipeline.make_pipeline(shape, CFG, mesh)
    args = pipeline._shard_inputs(np.ones(shape, np.int32), None, CFG, 1,
                                  info)
    names = _op_names(fn.lower(*args).compile())
    for scope in ("map", "sample", "shuffle", "sort", "refine"):
        assert f"/{scope}/" in names, scope
    refiner = pipeline.DeviceRefiner(_reads()[:16], CFG, mesh=mesh)
    ids = np.zeros(8, np.int32)
    names = _op_names(refiner._fn(8).lower(
        ids, ids, refiner._data, refiner._lens, refiner._halo).compile())
    for scope in ("sample", "shuffle", "sort", "refine"):
        assert f"/{scope}/" in names, scope
