"""Plain reference for the suffix-array cells: numpy only, and nothing
imported from the system under test.

A read set is an ``(r, l)`` array of tokens in ``1..vocab``; the end of a
read is ``0`` and sorts first.  A suffix is named by its global index
``row << stride | offset`` with ``stride = ceil(log2(l + 1))``, and the
suffix array lists all ``r * (l + 1)`` suffixes (the empty one at
``offset = l`` included) in lexicographic order of their tokens, ties by
global index.  ``lcp[i]`` is the number of tokens that ``sa[i - 1]`` and
``sa[i]`` share, ``lcp[0] = 0``.

The checks count defects instead of stopping at the first, so that a run
prints how far an answer is off beside the limit it is held to.
"""
from __future__ import annotations

import numpy as np


def stride_bits(read_len: int) -> int:
    return int(np.ceil(np.log2(read_len + 1)))


def permutation_defects(sa, r: int, l: int, stride: int) -> int:
    """Suffixes the array leaves out plus entries that name no suffix or
    one already named: 0 exactly when ``sa`` is a permutation of all
    ``r * (l + 1)`` suffixes."""
    n = r * (l + 1)
    sa = np.asarray(sa, np.int64).ravel()
    rows, offs = sa >> stride, sa & ((1 << stride) - 1)
    ok = (sa >= 0) & (rows < r) & (offs <= l)
    seen = np.bincount(rows[ok] * (l + 1) + offs[ok], minlength=n)
    distinct = int((seen > 0).sum())
    return (n - distinct) + (sa.size - distinct)


def adjacent_order(sa, reads, stride: int, chunk: int = 1 << 15):
    """``(pairs out of order, lcp)``: every adjacent pair is compared on
    the raw tokens (the end of a read first, ties by global index), and
    the LCP array is counted from the same compare.  Entries that name no
    suffix are left out (``permutation_defects`` counts them)."""
    r, l = reads.shape
    width = 2 * l + 1  # any suffix plus its terminator fits one row
    flat = np.zeros((r, width), np.uint8)
    flat[:, :l] = reads
    flat = flat.ravel()
    sa = np.asarray(sa, np.int64).ravel()
    rows, offs = sa >> stride, sa & ((1 << stride) - 1)
    sa = sa[(sa >= 0) & (rows < r) & (offs <= l)]
    lcp = np.zeros(sa.size, np.int64)
    cols = np.arange(l + 1, dtype=np.int64)
    bad = 0
    for lo in range(0, sa.size - 1, chunk):
        a = sa[lo : lo + chunk + 1]
        base = (a >> stride) * width + (a & ((1 << stride) - 1))
        toks = flat[base[:, None] + cols[None, :]]
        x, y = toks[:-1], toks[1:]
        neq = x != y
        has = neq.any(axis=1)
        first = neq.argmax(axis=1)
        idx = np.arange(first.size)
        ok = np.where(has, x[idx, first] < y[idx, first], a[:-1] < a[1:])
        bad += int((~ok).sum())
        # shared tokens: up to the first mismatch or the common end
        lcp[lo + 1 : lo + 1 + first.size] = (neq | (x == 0)).argmax(axis=1)
    return bad, lcp


def index_defects(sa, lcp, reads) -> dict:
    """The published index against the raw tokens: ``sa`` defects
    (permutation and order) and ``lcp`` entries that differ from the
    reference's count (a wrong length counts every entry)."""
    r, l = reads.shape
    stride = stride_bits(l)
    perm = permutation_defects(sa, r, l, stride)
    order, want = adjacent_order(sa, reads, stride)
    lcp = np.asarray(lcp, np.int64).ravel()
    if lcp.shape != want.shape:
        lcp_bad = max(lcp.size, want.size)
    else:
        lcp_bad = int((lcp != want).sum())
    return {"sa": perm + order, "lcp": lcp_bad}


def suffix_array(reads, depth=None):
    """The reference suffix array of a read set: all suffixes sorted on
    their first ``depth`` tokens (all of them when None), ties by global
    index.  Tokens are packed 21 to a 64-bit word (3 bits each)."""
    reads = np.asarray(reads)
    r, l = reads.shape
    stride = stride_bits(l)
    depth = l if depth is None else min(depth, l)
    padded = np.zeros((r, 2 * l + 1), np.uint64)
    padded[:, :l] = reads
    rows = np.repeat(np.arange(r, dtype=np.int64), l + 1)
    offs = np.tile(np.arange(l + 1, dtype=np.int64), r)
    gidx = (rows << stride) | offs
    words = []
    for w0 in range(0, depth, 21):
        word = np.zeros(rows.size, np.uint64)
        for j in range(w0, min(w0 + 21, depth)):
            word = (word << np.uint64(3)) | padded[rows, offs + j]
        words.append(word << np.uint64(3 * (21 - min(21, depth - w0))))
    # np.lexsort takes its primary key last
    return gidx[np.lexsort([gidx, *words[::-1]])]


class KmerOracle:
    """Brute-force k-mer occurrences of a read set: every k-mer start,
    base-5 coded and sorted once."""

    def __init__(self, reads, k: int, stride: int):
        reads = np.asarray(reads)
        r, l = reads.shape
        self.k, self.stride, self.n = k, stride, l - k + 1
        codes = np.zeros((r, self.n), np.int64)
        for j in range(k):
            codes = codes * 5 + reads[:, j : j + self.n]
        codes = codes.ravel()
        self.order = np.argsort(codes, kind="stable")
        self.codes = codes[self.order]

    def codes_of(self, pats) -> np.ndarray:
        """Base-5 codes of the first ``k`` tokens of each row of ``pats``."""
        pats = np.asarray(pats, np.int64)
        c = np.zeros(pats.shape[0], np.int64)
        for j in range(self.k):
            c = c * 5 + pats[:, j]
        return c

    def ranges(self, pats):
        c = self.codes_of(pats)
        return (np.searchsorted(self.codes, c, "left"),
                np.searchsorted(self.codes, c, "right"))

    def counts(self, pats) -> np.ndarray:
        lo, hi = self.ranges(pats)
        return hi - lo

    def locate(self, pats):
        """Per row: the ascending global indexes of every occurrence."""
        lo, hi = self.ranges(pats)
        out = []
        for a, b in zip(lo, hi, strict=True):
            pos = self.order[a:b]
            out.append(np.sort(((pos // self.n) << self.stride)
                               | (pos % self.n)))
        return out


def lookup_defects(pats, counts, located, oracle: KmerOracle) -> dict:
    """Answers of one batch against the brute force: queries whose count
    differs, queries whose located positions differ, and queries with
    either wrong (a missing answer counts as wrong)."""
    pats = np.asarray(pats, np.int64)
    q = pats.shape[0]
    want_n = oracle.counts(pats)
    counts = np.asarray(counts, np.int64).ravel()
    count_bad = (np.ones(q, bool) if counts.shape != want_n.shape
                 else counts != want_n)
    located = list(located)
    locate_bad = np.array([
        i >= len(located)
        or not np.array_equal(np.asarray(located[i], np.int64), w)
        for i, w in enumerate(oracle.locate(pats))], bool)
    extra = max(0, len(located) - q)
    return {"count": int(count_bad.sum()),
            "locate": int(locate_bad.sum()) + extra,
            "queries": int((count_bad | locate_bad).sum()) + extra}
