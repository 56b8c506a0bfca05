"""LCP arrays served by the corpus store (the query engine's O(m + log n) leg).

``lcp[i] = LCP(suffix SA[i-1], suffix SA[i])`` — the classic companion array
of a suffix array (Manber–Myers; Bingmann/Gog/Kurpicz treat it as a
first-class artifact of index construction).  The serving engine
(``repro.serve.sa_engine``) derives per-shard LLCP/RLCP range-minima from it
so a batched binary search compares only tokens the pattern has not already
matched.

Three producers, one definition:

* the single-pass device build names each pair's LCP in the refinement
  round that splits it (``build_suffix_array(lcp=True)``), and
  :func:`finish_lcp` fetches only the pairs it could not name: each
  device's first pair, ties left at the round cap, identical suffixes of
  variable-length reads;
* during the out-of-core merge, emit order **is** final order, so the merge
  sink computes each adjacent pair's LCP as pieces stream out
  (``SuperblockConfig.emit_lcp``; see ``core/superblock._OutputSink``);
* :func:`lcp_from_sa` recomputes the whole array post-hoc from any built SA
  (the reference the tests hold the others to).

The host's share reduces to :func:`pairwise_lcp`: progressive K-token
window fetches from the :class:`~repro.core.store.CorpusStore`, stopping at
the first token mismatch **or** the first position where both windows carry
the padding ``0`` (both suffixes ended — contents equal up to their common
length).  Real tokens are >= 1, so this is exact under the store's
zero-padding convention.
"""
from __future__ import annotations

import numpy as np

from repro.core.store import CorpusStore
from repro.core.types import LCP_UNNAMED


def pairwise_lcp(store: CorpusStore, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise LCP of suffix pairs ``(a[i], b[i])`` (global indexes).

    One batched store round per window depth still in play: pairs that
    resolved (mismatch or double end-of-suffix) drop out of deeper rounds,
    so traffic is proportional to actual tie depth, same as the merge's
    escalation.  Returns (m,) int64 token counts.
    """
    a = np.asarray(a, np.int64).ravel()
    b = np.asarray(b, np.int64).ravel()
    assert a.shape == b.shape, (a.shape, b.shape)
    m = a.shape[0]
    out = np.zeros(m, np.int64)
    if m == 0:
        return out
    live = np.arange(m, dtype=np.int64)
    k = store.k
    for depth in range(store.max_window_depth):
        if live.size == 0:
            return out
        wa = store.fetch_windows(a[live], depth)
        wb = store.fetch_windows(b[live], depth)
        stop = (wa != wb) | ((wa == 0) & (wb == 0))
        resolved = stop.any(axis=1)
        first = np.argmax(stop, axis=1)
        out[live] += np.where(resolved, first, k)
        live = live[~resolved]
    if live.size:
        raise RuntimeError("pairwise LCP overran the window bound")
    return out


def lcp_from_sa(store: CorpusStore, sa: np.ndarray,
                batch: int = 1 << 16) -> np.ndarray:
    """Full LCP array of a sorted SA: ``lcp[0] = 0``,
    ``lcp[i] = LCP(sa[i-1], sa[i])``; adjacent pairs in ``batch``-sized
    slices so the working set stays bounded for memmapped SAs."""
    sa = np.asarray(sa)
    n = sa.shape[0]
    out = np.zeros(n, np.int64)
    for lo in range(1, n, batch):
        hi = min(lo + batch, n)
        out[lo:hi] = pairwise_lcp(
            store,
            np.asarray(sa[lo - 1 : hi - 1], np.int64),
            np.asarray(sa[lo:hi], np.int64),
        )
    return out


def finish_lcp(store: CorpusStore, sa: np.ndarray, lcp: np.ndarray,
               batch: int = 1 << 16) -> int:
    """Fill the ``LCP_UNNAMED`` entries of ``lcp`` (the SA's LCP array as
    the device build named it) in place, by :func:`pairwise_lcp` on those
    pairs only; returns how many it filled."""
    at = np.flatnonzero(lcp[1:] == LCP_UNNAMED) + 1
    for lo in range(0, at.size, batch):
        i = at[lo : lo + batch]
        lcp[i] = pairwise_lcp(store, np.asarray(sa[i - 1], np.int64),
                              np.asarray(sa[i], np.int64))
    return int(at.size)
