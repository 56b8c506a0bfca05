from repro.data.chunk_store import (
    ChunkedCorpusMeta,
    ChunkedCorpusReader,
    chunk_items_for_budget,
    default_chunk_items,
    read_chunked_corpus_meta,
    write_chunked_corpus,
    write_chunked_stream,
)
from repro.data.corpus import synth_dna_reads, synth_token_corpus
from repro.data.dedup import dedup_corpus, find_duplicate_spans

__all__ = [
    "synth_dna_reads",
    "synth_token_corpus",
    "dedup_corpus",
    "find_duplicate_spans",
    "ChunkedCorpusMeta",
    "ChunkedCorpusReader",
    "chunk_items_for_budget",
    "default_chunk_items",
    "read_chunked_corpus_meta",
    "write_chunked_corpus",
    "write_chunked_stream",
]
