"""Distributed suffix-array construction — the paper's scheme (§IV).

Dataflow per device (inside ``shard_map`` over a flat 1-D mesh):

  Map      : every local suffix -> 16-byte record (prefix key + packed index)
             [repro.core.encoding / kernels.prefix_pack]
  Sample   : TeraSort-style splitter estimation  [distributed.sample_splitters]
  Shuffle  : one all_to_all of records — *indexes move, suffixes stay put*
  Reduce   : sort by (key, index); tie groups refine by fetching the next
             K-token window from the in-memory store (mgetsuffix) inside a
             lax.while_loop until psum(ties)==0
  Output   : per-device sorted index runs == the global suffix array

Static-shape discipline (TPU): the shuffle capacity is sized *exactly* by a
cheap histogram pre-pass (``cfg.adaptive``, two-phase planning — the TPU
analogue of the paper's up-front sampling).  Without it the capacity is the
static ``shuffle_slack`` share, one compiled program per job shape; a job
whose shuffle overflows it is rebuilt once at the exact capacity, so no
suffix is ever left out of the result.  Store fetches that overflow their
capacity are retried with **group-synchronous advancement**: a tie group only
consumes its next K-token window when every active member's request was
served, so partial service can never produce an inconsistent comparison.

The same entry point drives the read-set mode (the paper's bioinformatics
case, incl. paired-end: concatenate both files' reads) and the long-text
mode (LM-corpus dedup).
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import SAConfig
from repro.core import encoding
from repro.core.distributed import (
    bucket_scatter,
    exchange,
    lex_bucket,
    lex_sort,
    run_all,
    run_starts,
    sample_splitters,
)
from repro.core.store import StoreSpec, mget_window, token_bytes
from repro.core.trace import span
from repro.core.types import (
    KEY_SENTINEL,
    LCP_UNNAMED,
    WORD_BITS,
    WORD_MOD,
    Footprint,
    SAResult,
    global_index,
    unpack_index,
)

AXIS = "sa"


def _flat_mesh(mesh: Optional[Mesh]) -> Mesh:
    if mesh is not None:
        return mesh
    devs = np.array(jax.devices())
    return Mesh(devs, (AXIS,))


def _tied(g: jnp.ndarray) -> jnp.ndarray:
    prev = jnp.concatenate([jnp.array([-1], g.dtype), g[:-1]])
    nxt = jnp.concatenate([g[1:], jnp.array([-2], g.dtype)])
    return (g == prev) | (g == nxt)


def _remaining(ih, il, *, text_mode, text_len, uniform_len, stride_bits):
    """Tokens left in each suffix, where that is locally computable (text
    mode / uniform-length reads)."""
    if text_mode:
        return text_len - il
    _, off = unpack_index(ih, il, stride_bits)
    return uniform_len - off


def _suffix_exhausted(ih, il, depth, *, text_mode, text_len, uniform_len,
                      stride_bits, k):
    """Analytic exhaustion: the first ``depth * k`` tokens already covered the
    whole suffix (locally computable in text mode / uniform-length reads)."""
    rem = _remaining(ih, il, text_mode=text_mode, text_len=text_len,
                     uniform_len=uniform_len, stride_bits=stride_bits)
    return rem <= depth * k


def _shared_tokens(hi, lo, cfg):
    """Per position p >= 1: the leading tokens the packed K-token keys at
    p - 1 and p have in common — elementwise compares of the key words'
    prefixes, no gather.  Where the two keys differ this is the count the
    two suffixes share: a key's tokens after its suffix's end are all
    ``0``, so the keys differ before any end they both carry."""
    cpw = cfg.resolved_chars_per_word()
    bits = max(1, cfg.vocab_size.bit_length())

    def common(a, b):  # the longest common prefix of two words, in tokens
        n = jnp.zeros(a.shape, jnp.int32)
        for j in range(1, cpw + 1):  # prefixes as encoding.pack_columns packs
            if cfg.packing == "base":
                q = (cfg.vocab_size + 1) ** (cpw - j)
                n = n + (a // q == b // q)
            else:
                s = 31 - bits * j
                n = n + ((a >> s) == (b >> s))
        return n

    n = common(hi[:-1], hi[1:])
    return n + jnp.where(n == cpw, common(lo[:-1], lo[1:]), 0)


@jax.named_scope("refine")
def _refine_tie_groups(g, ih, il, exhausted, *, store_local, spec, cfg,
                       analytic, text_mode, text_len, uniform_len,
                       stride_bits, hard_cap, lcp=None):
    """Group-synchronous window-refinement loop (the reduce-phase core).

    Shared by the full pipeline (:func:`_device_fn`) and the out-of-core
    merge's device-side bucket refinement (:func:`_refiner_fn`): still-tied
    groups of suffixes fetch their next K-token window from the store
    (``mget_window``) and re-sort within the group, a group only consuming a
    window when every active member was served.  Runs under ``shard_map``;
    returns the final ``(g, ih, il, exhausted, depth, stats)`` carry.

    Given ``lcp`` (``(n,)`` int32 by sorted position, the pairs named so
    far), the loop also carries it and returns it last.  A group's start
    never moves once it exists, and two neighbouring groups share exactly
    their common windows, so the LCP across a boundary is final in the
    round the boundary first appears: the group's depth before that round
    times K plus the leading tokens the two new windows share.  Without it
    the traced program is the loop alone.
    """
    axis = spec.axis
    n = ih.shape[0]
    k = cfg.prefix_len

    zero = lax.pcast(jnp.int32(0), (axis,), to="varying")
    depth0 = lax.pcast(jnp.ones((n,), jnp.int32), (axis,), to="varying")  # K tokens consumed
    stats0 = dict(
        iters=zero,
        fetch_requests=zero,
        fetch_request_bytes=zero,
        fetch_response_bytes=zero,
        retries=zero,
        max_depth=zero + 1,
    )

    def cond(carry):
        g, ih, il, exhausted, depth, stats, *_ = carry
        active = _tied(g) & ~exhausted & (ih != KEY_SENTINEL)
        total = lax.psum(jnp.sum(active), axis)
        return (total > 0) & (stats["iters"] < hard_cap)

    def body(carry):
        g, ih, il, exhausted, depth, stats, *named = carry
        validr = ih != KEY_SENTINEL
        if analytic:
            exhausted = _suffix_exhausted(
                ih, il, depth, text_mode=text_mode, text_len=text_len,
                uniform_len=uniform_len, stride_bits=stride_bits, k=k,
            ) | ~validr
        active = _tied(g) & ~exhausted & validr
        if text_mode:
            row = il + depth * k  # absolute window start owns the request
            off = jnp.zeros_like(il)
        else:
            row, off0 = unpack_index(ih, il, stride_bits)
            off = off0 + depth * k
        resp, exh_new, ok, fs = mget_window(store_local, row, off, active, spec, cfg)
        if cfg.server_pack:
            words = resp  # packed server-side (beyond-paper compression)
        else:
            words = encoding.pack_columns(resp, cfg)
        # group-synchronous advance: a group consumes its window only if every
        # active member was served; otherwise the whole group retries.
        adv = run_all(jnp.where(active, ok, True), g) & validr
        nk_hi = jnp.where(adv & active, words[0], 0)
        nk_lo = jnp.where(adv & active, words[1], 0)
        if not analytic:
            exhausted = jnp.where(adv & active, exh_new, exhausted)
        depth = jnp.where(adv & active, depth + 1, depth)
        exh_i = exhausted.astype(jnp.int32)
        g, nk_hi, nk_lo, ih, il, exh_i, depth = lex_sort(
            (g, nk_hi, nk_lo, ih, il, exh_i, depth), num_keys=5
        )
        exhausted = exh_i > 0
        validr = ih != KEY_SENTINEL
        eq = jnp.concatenate(
            [
                jnp.array([False]),
                (g[1:] == g[:-1])
                & (nk_hi[1:] == nk_hi[:-1])
                & (nk_lo[1:] == nk_lo[:-1]),
            ]
        )
        eq = eq & validr
        if named:
            # a boundary appears at p: p - 1 and p were one group until now.
            # p's window sorts above p - 1's, so p advanced this round and
            # its depth is the group's plus one.
            split = (g[1:] == g[:-1]) & ~eq[1:]
            at = (depth[1:] - 1) * k + _shared_tokens(nk_hi, nk_lo, cfg)
            named = [jnp.concatenate(
                [named[0][:1], jnp.where(split, at, named[0][1:])])]
        g = run_starts(eq)
        stats = dict(
            iters=stats["iters"] + 1,
            fetch_requests=stats["fetch_requests"] + fs.requests,
            fetch_request_bytes=stats["fetch_request_bytes"] + fs.request_bytes,
            fetch_response_bytes=stats["fetch_response_bytes"] + fs.response_bytes,
            retries=stats["retries"] + fs.dropped,
            max_depth=jnp.maximum(stats["max_depth"], jnp.max(depth)),
        )
        return (g, ih, il, exhausted, depth, stats, *named)

    named = () if lcp is None else (lcp,)
    return lax.while_loop(cond, body,
                          (g, ih, il, exhausted, depth0, stats0, *named))


def _map_phase(reads_l, lengths_l, halo_l, *, cfg, rows_per_shard, stride_bits,
               text_mode, text_len):
    """Map + sample + bucket (shared by the histogram pre-pass and main run)."""
    rec, valid0 = _map_records(
        reads_l, lengths_l, halo_l, cfg=cfg, rows_per_shard=rows_per_shard,
        stride_bits=stride_bits, text_mode=text_mode, text_len=text_len,
    )
    with jax.named_scope("sample"):
        s_hi, s_lo = sample_splitters(rec[0], rec[1], cfg.samples_per_shard,
                                      AXIS)
        bucket = lex_bucket(rec[0], rec[1], s_hi, s_lo)
        # invalid padding records go to a local dump bucket, never shipped
        nb = lax.axis_size(AXIS)
        bucket = jnp.where(valid0.reshape(-1), bucket, jnp.int32(nb))
    return rec, valid0, bucket


@jax.named_scope("map")
def _map_records(reads_l, lengths_l, halo_l, *, cfg, rows_per_shard,
                 stride_bits, text_mode, text_len):
    """Map: each local suffix's record (packed K-token key, global index)
    and whether it is a real suffix."""
    me = lax.axis_index(AXIS)
    if text_mode:
        flat = jnp.concatenate([reads_l.reshape(-1), halo_l.reshape(-1)])
        if cfg.use_pallas:
            from repro.kernels import ops as kops

            keys = kops.prefix_pack(flat, cfg)[:rows_per_shard]
            pos_col = (
                jnp.arange(rows_per_shard, dtype=jnp.int32) + me * rows_per_shard
            )
            rec = jnp.stack(
                [keys[:, 0], keys[:, 1], jnp.zeros_like(pos_col), pos_col]
            )
        else:
            rec = encoding.make_records_text(
                flat, cfg, pos_base=me * rows_per_shard, n_emit=rows_per_shard
            )
        pos = jnp.arange(rows_per_shard, dtype=jnp.int32) + me * rows_per_shard
        valid0 = pos < text_len
        rec = jnp.where(valid0[None, :], rec, KEY_SENTINEL)
    else:
        rec, valid0 = encoding.make_records_reads(
            reads_l,
            lengths_l,
            cfg,
            read_id_base=me * rows_per_shard,
            stride_bits=stride_bits,
        )
        rec = jnp.where(valid0[None, :], rec, KEY_SENTINEL)
    return rec, valid0


def _hist_fn(reads_l, lengths_l, halo_l, *, cfg, num_shards, rows_per_shard,
             stride_bits, text_mode, text_len, **_):
    """Pre-pass: per-(sender,bucket) record counts -> exact shuffle capacity."""
    _, _, bucket = _map_phase(
        reads_l, lengths_l, halo_l, cfg=cfg, rows_per_shard=rows_per_shard,
        stride_bits=stride_bits, text_mode=text_mode, text_len=text_len,
    )
    hist = jnp.bincount(bucket, length=num_shards + 1)[:num_shards]
    return hist[None, :].astype(jnp.int32)


def _device_fn(
    reads_l: jnp.ndarray,
    lengths_l: jnp.ndarray,
    halo_l: jnp.ndarray,
    *,
    cfg: SAConfig,
    num_shards: int,
    rows_per_shard: int,
    row_len: int,
    stride_bits: int,
    shuffle_cap: int,
    fetch_cap: int,
    max_rounds: int,
    uniform_len: Optional[int],
    text_mode: bool,
    text_len: int,
    lcp: bool = False,
):
    """Per-device SA pipeline body (runs under shard_map).

    With ``lcp`` it also returns each sorted position's LCP with the one
    before it: named by the first sort's keys and by the refinement round
    that splits the pair, and for identical suffixes, analytically, by
    their length.  Pairs it cannot name hold ``LCP_UNNAMED``: every
    device's first position (its neighbour is on another device), pairs
    still tied at ``hard_cap``, and identical suffixes of variable-length
    reads.
    """
    d = num_shards
    k = cfg.prefix_len

    rec, valid0, bucket = _map_phase(
        reads_l, lengths_l, halo_l, cfg=cfg, rows_per_shard=rows_per_shard,
        stride_bits=stride_bits, text_mode=text_mode, text_len=text_len,
    )
    n_valid_local = jnp.sum(valid0).astype(jnp.int32)

    # ---- Shuffle: the 16-byte-record all_to_all ----------------------
    with jax.named_scope("shuffle"):
        buf, slot, _ = bucket_scatter(rec, bucket, d + 1, shuffle_cap,
                                      KEY_SENTINEL)
        recv = exchange(buf[:, :d], AXIS).reshape(4, d * shuffle_cap)
    drop_shuffle = jnp.sum(
        valid0.reshape(-1) & (slot >= d * shuffle_cap)
    ).astype(jnp.int32)

    # ---- Reduce: initial sort ----------------------------------------
    kh, kl, ih, il = recv
    with jax.named_scope("sort"):
        kh, kl, ih, il = lex_sort((kh, kl, ih, il), num_keys=4)
    validr = ih != KEY_SENTINEL

    eq = jnp.concatenate(
        [jnp.array([False]), (kh[1:] == kh[:-1]) & (kl[1:] == kl[:-1])]
    )
    eq = eq & validr
    g = run_starts(eq)
    lcp0 = None
    if lcp:
        lcp0 = jnp.concatenate([
            jnp.full((1,), LCP_UNNAMED, jnp.int32),
            jnp.where(eq[1:] | ~validr[1:], LCP_UNNAMED,
                      _shared_tokens(kh, kl, cfg)),
        ])

    # exhausted = the first depth*K tokens already covered the whole suffix.
    # Analytic when remaining length is locally computable (text mode /
    # uniform reads — the paper's skip-the-short-suffixes trick, §IV-B);
    # variable-length reads resolve lazily via fetch-response flags.
    analytic = text_mode or (uniform_len is not None)

    if analytic:
        exhausted = _suffix_exhausted(
            ih, il, jnp.int32(1), text_mode=text_mode, text_len=text_len,
            uniform_len=uniform_len, stride_bits=stride_bits, k=k,
        )
    else:
        exhausted = jnp.zeros_like(validr)  # resolved lazily via fetch flags
    exhausted = exhausted | ~validr

    spec = StoreSpec(
        axis=AXIS,
        num_shards=d,
        rows_per_shard=rows_per_shard,
        row_len=row_len,
        request_capacity=fetch_cap,
    )
    # text mode: local store shard = tokens + right halo so windows starting
    # near the shard boundary stay a single-owner lookup.
    if text_mode:
        store_local = jnp.concatenate([reads_l.reshape(-1), halo_l.reshape(-1)])
        store_local = store_local[:, None]
    else:
        store_local = reads_l

    g, ih, il, exhausted, depth, stats, *named = _refine_tie_groups(
        g, ih, il, exhausted, store_local=store_local, spec=spec, cfg=cfg,
        analytic=analytic, text_mode=text_mode, text_len=text_len,
        uniform_len=uniform_len, stride_bits=stride_bits,
        hard_cap=2 * max_rounds + 8, lcp=lcp0,
    )

    # unresolved = groups still tied and not exhausted when hard_cap hit
    unresolved = jnp.sum(
        _tied(g) & ~exhausted & (ih != KEY_SENTINEL)
    ).astype(jnp.int32)
    count = jnp.sum(ih != KEY_SENTINEL).astype(jnp.int32)
    statvec = jnp.stack(
        [
            count,
            n_valid_local,
            stats["iters"],
            stats["fetch_requests"],
            stats["fetch_request_bytes"],
            stats["fetch_response_bytes"],
            drop_shuffle,
            stats["retries"],
            unresolved,
            stats["max_depth"],
        ]
    )
    if not lcp:
        return ih, il, statvec[None, :]
    (lcp_named,) = named
    if analytic:
        # still tied and ended: identical suffixes, sharing their length
        kw = dict(text_mode=text_mode, text_len=text_len,
                  uniform_len=uniform_len, stride_bits=stride_bits)
        ended = _suffix_exhausted(ih, il, depth, k=k, **kw)
        same = (g[1:] == g[:-1]) & ended[1:] & ended[:-1]
        lcp_named = jnp.concatenate([
            lcp_named[:1],
            jnp.where(same, _remaining(ih, il, **kw)[1:], lcp_named[1:]),
        ])
    return ih, il, statvec[None, :], lcp_named


def plan(corpus_shape, cfg: SAConfig, num_shards: int, lengths=None):
    """Static planning shared by run and dry-run paths."""
    text_mode = len(corpus_shape) == 1
    if text_mode:
        n = corpus_shape[0]
        rows_per_shard = -(-n // num_shards)
        row_len, l = 1, 1
        stride_bits = 0
        n_local = rows_per_shard
        text_len = n
        uniform_len = None
    else:
        r, l = corpus_shape
        rows_per_shard = -(-r // num_shards)
        row_len = l
        stride_bits = int(math.ceil(math.log2(l + 1)))
        n_local = rows_per_shard * (l + 1)
        text_len = 0
        uniform_len = l if lengths is None else None
    shuffle_cap = max(1, int(math.ceil(n_local * cfg.shuffle_slack / num_shards)))
    if cfg.max_rounds:
        max_rounds = cfg.max_rounds
    elif text_mode:
        max_rounds = int(math.ceil(corpus_shape[0] / cfg.prefix_len)) + 1
    else:
        max_rounds = int(math.ceil((l + 1) / cfg.prefix_len)) + 1
    return dict(
        text_mode=text_mode,
        rows_per_shard=rows_per_shard,
        row_len=row_len,
        stride_bits=stride_bits,
        shuffle_cap=shuffle_cap,
        max_rounds=max_rounds,
        uniform_len=uniform_len,
        text_len=text_len,
        n_local=n_local,
    )


def _shard_inputs(corpus, lengths, cfg: SAConfig, d: int, info):
    corpus = np.asarray(corpus, np.int32)
    rows = info["rows_per_shard"]
    k = cfg.prefix_len
    if info["text_mode"]:
        pad = rows * d - corpus.shape[0]
        flat = np.pad(corpus, (0, pad))
        data = flat.reshape(d * rows, 1)
        lens = np.zeros((d * rows,), np.int32)
        halo = np.zeros((d, k), np.int32)
        for i in range(d - 1):
            seg = flat[(i + 1) * rows : min((i + 1) * rows + k, d * rows)]
            halo[i, : seg.shape[0]] = seg
        halo = halo.reshape(d * k)
    else:
        r, l = corpus.shape
        pad = rows * d - r
        data = np.pad(corpus, ((0, pad), (0, 0)))
        if lengths is None:
            lens = np.concatenate(
                [np.full((r,), l, np.int32), np.full((pad,), -1, np.int32)]
            )
        else:
            lens = np.concatenate(
                [np.asarray(lengths, np.int32), np.full((pad,), -1, np.int32)]
            )
        halo = np.zeros((d,), np.int32)
    return data, lens, halo


def make_pipeline(corpus_shape, cfg: SAConfig, mesh: Mesh, lengths=None,
                  shuffle_cap: Optional[int] = None, lcp: bool = False):
    """Build the jitted shard_map'd pipeline for given static shapes.

    Returns (jitted_fn, info).  Usable both for execution and for
    ``.lower()`` in the multi-pod dry-run.  The jitted program is cached
    per static configuration, so equal-shaped builds (the superblocks of
    one out-of-core build) trace and compile once.  With ``lcp`` the
    program also returns the LCP its refinement names (:func:`_device_fn`).
    """
    fn, info = _pipeline_program(tuple(corpus_shape), cfg, mesh,
                                 lengths is None, shuffle_cap, lcp)
    return fn, dict(info)


@lru_cache(maxsize=16)
def _pipeline_program(corpus_shape, cfg: SAConfig, mesh: Mesh,
                      uniform: bool, shuffle_cap: Optional[int], lcp: bool):
    d = mesh.devices.size
    info = plan(corpus_shape, cfg, d)
    if not uniform:
        info["uniform_len"] = None
    if shuffle_cap is not None:
        info["shuffle_cap"] = shuffle_cap
    fetch_cap = max(
        1,
        int(math.ceil(d * info["shuffle_cap"] * cfg.fetch_fraction
                      * cfg.shuffle_slack / d)),
    )
    fn = partial(
        _device_fn,
        cfg=cfg,
        num_shards=d,
        rows_per_shard=info["rows_per_shard"],
        row_len=info["row_len"],
        stride_bits=info["stride_bits"],
        shuffle_cap=info["shuffle_cap"],
        fetch_cap=fetch_cap,
        max_rounds=info["max_rounds"],
        uniform_len=info["uniform_len"],
        text_mode=info["text_mode"],
        text_len=info["text_len"],
        lcp=lcp,
    )
    smapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS),) * (4 if lcp else 3),
        # interpret-mode Pallas mixes varying/unvarying internals; relax the
        # vma checker when kernels are routed through pallas_call.
        check_vma=not cfg.use_pallas,
    )
    return jax.jit(smapped), info


@lru_cache(maxsize=16)
def _hist_program(corpus_shape, cfg: SAConfig, mesh: Mesh):
    """Jitted histogram pre-pass, cached like :func:`_pipeline_program`."""
    d = mesh.devices.size
    info = plan(corpus_shape, cfg, d)
    fn = partial(
        _hist_fn,
        cfg=cfg,
        num_shards=d,
        rows_per_shard=info["rows_per_shard"],
        stride_bits=info["stride_bits"],
        text_mode=info["text_mode"],
        text_len=info["text_len"],
    )
    smapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(AXIS), P(AXIS), P(AXIS)), out_specs=P(AXIS),
        check_vma=not cfg.use_pallas,
    )
    return jax.jit(smapped)


def _exact_shuffle_cap(corpus_shape, cfg, mesh, data, lens, halo) -> int:
    """Histogram pre-pass: exact max per-(sender,bucket) count."""
    hist_fn = _hist_program(tuple(corpus_shape), cfg, mesh)
    hist = np.asarray(hist_fn(data, lens, halo))
    return max(1, int(hist.max()))


def build_suffix_array(
    corpus,
    lengths=None,
    cfg: SAConfig = SAConfig(),
    mesh: Optional[Mesh] = None,
    lcp: bool = False,
) -> SAResult:
    """Build the suffix array of ``corpus`` with the paper's scheme.

    corpus: (R, L) int32 reads (tokens 1..V, 0 padding) or (n,) int32 text.
    ``lcp``: the caller publishes an LCP array, so the device names it as
    it sorts; ``SAResult.lcp`` then holds ``LCP_UNNAMED`` at the pairs it
    could not name (stats ``lcp_device_pairs`` counts the others), for
    :func:`repro.core.lcp.finish_lcp` to fill.
    """
    mesh = _flat_mesh(mesh)
    d = mesh.devices.size
    timers = {"t_device_s": 0.0}
    with span("build.stage"):
        info = plan(np.shape(corpus), cfg, d, lengths)
        data, lens, halo = _shard_inputs(corpus, lengths, cfg, d, info)
        sharding = NamedSharding(mesh, P(AXIS))
        data = jax.device_put(data, sharding)
        lens = jax.device_put(lens, sharding)
        halo = jax.device_put(halo, sharding)
    def run(shuffle_cap):
        jitted, info = make_pipeline(
            np.shape(corpus), cfg, mesh, lengths, shuffle_cap=shuffle_cap,
            lcp=lcp,
        )
        ih, il, statmat, *named = jitted(data, lens, halo)
        return (np.asarray(ih), np.asarray(il), np.asarray(statmat),
                [np.asarray(x) for x in named], d * d * info["shuffle_cap"])

    def exact_cap():
        return _exact_shuffle_cap(np.shape(corpus), cfg, mesh, data, lens,
                                  halo)

    # the device's share: histogram pre-pass, pipeline, outputs on the host
    with span("build.device", timers, "t_device_s"):
        ih, il, statmat, named, slots = run(
            exact_cap() if cfg.adaptive else None)
        dropped = int(statmat[:, 6].sum())
        if dropped:
            # the static capacity overflowed: a short suffix array is never
            # returned.  The exact capacity drops nothing by construction.
            with span("build.rebuild"):
                ih, il, statmat, named, more = run(exact_cap())
            slots += more
            if int(statmat[:, 6].sum()):
                raise RuntimeError(
                    "shuffle dropped records at the exact capacity")
    with span("build.finalize"):
        res = _finalize(ih, il, statmat, corpus, cfg, *named)
    res.stats.update(timers)
    # the all_to_all's record slots over every attempt, and the valid
    # records the last one delivered (== num_suffixes)
    res.stats.update(
        shuffle_slots=slots,
        shuffle_records=int(statmat[:, 0].sum()),
        shuffle_dropped=dropped,
        shuffle_rebuilds=int(dropped > 0),
    )
    return res


def _finalize(ih, il, statmat, corpus, cfg: SAConfig,
              lcp_dev=None) -> SAResult:
    d = statmat.shape[0]
    per_dev = ih.shape[0] // d
    valid = [slice(i * per_dev, i * per_dev + int(statmat[i, 0]))
             for i in range(d)]
    chunks = [global_index(ih[v], il[v]) for v in valid]
    sa = np.concatenate(chunks) if chunks else np.zeros((0,), np.int64)
    lcp = None
    if lcp_dev is not None:
        lcp = np.concatenate([lcp_dev[v] for v in valid]).astype(np.int64)
        lcp[:1] = 0

    corpus = np.asarray(corpus)
    tb = token_bytes(cfg.vocab_size)
    n_suffix = int(statmat[:, 1].sum())
    fp = Footprint(
        input=int(corpus.size) * tb,
        store_put=int(corpus.size) * tb,
        shuffle=n_suffix * 16,
        fetch_request=int(statmat[:, 4].sum()),
        fetch_response=int(statmat[:, 5].sum()),
        materialized=0,
        output=n_suffix * 8,
        rounds=int(statmat[:, 9].max()) if d else 0,
        dropped=int(statmat[:, 6].sum()),
    )
    stats = {
        "num_suffixes": n_suffix,
        "emitted": int(sa.shape[0]),
        "per_device_counts": statmat[:, 0].tolist(),
        "fetch_requests": int(statmat[:, 3].sum()),
        "iters": int(statmat[:, 2].max()),
        "rounds": fp.rounds,
        "dropped": fp.dropped,
        "retries": int(statmat[:, 7].sum()),
        "unresolved": int(statmat[:, 8].sum()),
    }
    if lcp is not None:
        stats["lcp_device_pairs"] = int((lcp[1:] != LCP_UNNAMED).sum())
    return SAResult(suffix_array=sa, footprint=fp, stats=stats, lcp=lcp)


# ---------------------------------------------------------------------------
# Device-side index-set refinement (the out-of-core merge's device backend)
# ---------------------------------------------------------------------------


def _refiner_fn(
    idx_hi: jnp.ndarray,
    idx_lo: jnp.ndarray,
    reads_l: jnp.ndarray,
    lengths_l: jnp.ndarray,
    halo_l: jnp.ndarray,
    *,
    cfg: SAConfig,
    num_shards: int,
    rows_per_shard: int,
    row_len: int,
    stride_bits: int,
    cap: int,
    max_rounds: int,
    uniform_len: Optional[int],
    text_mode: bool,
    text_len: int,
):
    """Per-device body ranking an arbitrary suffix-index set (under shard_map).

    The device analogue of the host merge's ``_refine_sort``: each device
    holds a slice of the index set (padding slots carry ``idx_hi == -1``),
    fetches the depth-0 windows remotely via :func:`mget_window`, sample-sorts
    the resulting 16-byte records across the axis (equal keys colocate), and
    refines still-tied groups with the same loop as the pipeline reducer.

    ``cap`` is the per-device slice length.  Capacities are sized for zero
    drops: the record shuffle needs only ``cap`` per bucket (a device sends
    at most its ``cap`` input records), but the refinement loop runs *after*
    sample-sort colocation, where one device can hold up to ``d * cap`` tied
    records whose window requests may all target one owner shard — so the
    fetch capacity must be ``d * cap``.  No retry rounds occur and the
    result is deterministic in one pass.
    """
    d = num_shards
    k = cfg.prefix_len
    valid0 = idx_hi >= 0

    spec = StoreSpec(
        axis=AXIS,
        num_shards=d,
        rows_per_shard=rows_per_shard,
        row_len=row_len,
        request_capacity=d * cap,
    )
    if text_mode:
        store_local = jnp.concatenate([reads_l.reshape(-1), halo_l.reshape(-1)])
        store_local = store_local[:, None]
        row = jnp.where(valid0, idx_lo, 0)
        off = jnp.zeros_like(idx_lo)
    else:
        store_local = reads_l
        row, off = unpack_index(idx_hi, idx_lo, stride_bits)

    # depth-0 windows for the local slice (remote fetch: the indexes are
    # arbitrary, their tokens live on whichever device owns them)
    with jax.named_scope("sample"):
        win, exh0, ok, fs0 = mget_window(store_local, row, off, valid0, spec,
                                         cfg)
    words = win if cfg.server_pack else encoding.pack_columns(win, cfg)
    kh = jnp.where(valid0, words[0], KEY_SENTINEL)
    kl = jnp.where(valid0, words[1], KEY_SENTINEL)

    # sample-sort the records across the axis: equal initial keys colocate
    # (lex_bucket is strict-less-than), so all further refinement is local.
    rec = jnp.stack(
        [kh, kl,
         jnp.where(valid0, idx_hi, KEY_SENTINEL),
         jnp.where(valid0, idx_lo, KEY_SENTINEL),
         exh0.astype(jnp.int32)],
    )
    with jax.named_scope("sample"):
        s_hi, s_lo = sample_splitters(kh, kl, cfg.samples_per_shard, AXIS)
        bucket = jnp.where(valid0, lex_bucket(kh, kl, s_hi, s_lo),
                           jnp.int32(d))
    with jax.named_scope("shuffle"):
        buf, slot, _ = bucket_scatter(rec, bucket, d + 1, cap, KEY_SENTINEL)
        recv = exchange(buf[:, :d], AXIS).reshape(5, d * cap)
    drop = jnp.sum(valid0 & (slot >= d * cap)).astype(jnp.int32)
    kh, kl, ih, il, exh_i = recv
    with jax.named_scope("sort"):
        kh, kl, ih, il, exh_i = lex_sort((kh, kl, ih, il, exh_i), num_keys=4)
    validr = ih != KEY_SENTINEL

    eq = jnp.concatenate(
        [jnp.array([False]), (kh[1:] == kh[:-1]) & (kl[1:] == kl[:-1])]
    )
    eq = eq & validr
    g = run_starts(eq)

    analytic = text_mode or (uniform_len is not None)
    if analytic:
        exhausted = _suffix_exhausted(
            ih, il, jnp.int32(1), text_mode=text_mode, text_len=text_len,
            uniform_len=uniform_len, stride_bits=stride_bits, k=k,
        )
    else:
        exhausted = exh_i > 0  # resolved by the depth-0 fetch flags
    exhausted = exhausted | ~validr

    g, ih, il, exhausted, depth, stats = _refine_tie_groups(
        g, ih, il, exhausted, store_local=store_local, spec=spec, cfg=cfg,
        analytic=analytic, text_mode=text_mode, text_len=text_len,
        uniform_len=uniform_len, stride_bits=stride_bits,
        hard_cap=2 * max_rounds + 8,
    )

    unresolved = jnp.sum(
        _tied(g) & ~exhausted & (ih != KEY_SENTINEL)
    ).astype(jnp.int32)
    count = jnp.sum(ih != KEY_SENTINEL).astype(jnp.int32)
    statvec = jnp.stack(
        [
            count,
            stats["fetch_requests"] + fs0.requests,
            stats["fetch_request_bytes"] + fs0.request_bytes,
            stats["fetch_response_bytes"] + fs0.response_bytes,
            stats["iters"] + 1,  # service rounds incl. the depth-0 fetch
            stats["retries"] + fs0.dropped + drop,
            unresolved,
            stats["max_depth"],
        ]
    )
    return ih, il, statvec[None, :]


class DeviceRefiner:
    """Device-resident ranking of arbitrary suffix-index sets.

    The out-of-core merge's ``merge_backend="device"`` seam: wherever the
    host merge would rank a batch of global suffix indexes with numpy
    (splitter pools, oversized merge buckets, text-mode boundary re-ranks),
    this class runs the same group-synchronous window-refinement loop
    TPU-resident under ``shard_map``, windows served by ``mget_window`` from
    the device-sharded corpus — the merge never leaves the accelerator for
    bucket ranking.

    Jitted refiner programs are cached per padded batch size (sizes round up
    to the next power of two per device, so a merge compiles O(log capacity)
    programs, not one per bucket).  Fetch-byte accounting accumulates across
    calls and is folded into the merge's ``merge_fetch_bytes``.
    """

    def __init__(self, corpus, cfg: SAConfig, lengths=None, mesh=None):
        self.cfg = cfg
        self.mesh = _flat_mesh(mesh)
        self.d = self.mesh.devices.size
        corpus = np.asarray(corpus, np.int32)
        self.info = plan(corpus.shape, cfg, self.d, lengths)
        data, lens, halo = _shard_inputs(corpus, lengths, cfg, self.d, self.info)
        sharding = NamedSharding(self.mesh, P(AXIS))
        self._data = jax.device_put(data, sharding)
        self._lens = jax.device_put(lens, sharding)
        self._halo = jax.device_put(halo, sharding)
        self._fns = {}
        # accounting (read by the superblock merge)
        self.requests = 0
        self.request_bytes = 0
        self.response_bytes = 0
        self.rounds = 0
        self.retries = 0
        self.peak_records = 0
        self.calls = 0

    def _fn(self, per_dev: int):
        fn = self._fns.get(per_dev)
        if fn is None:
            body = partial(
                _refiner_fn,
                cfg=self.cfg,
                num_shards=self.d,
                rows_per_shard=self.info["rows_per_shard"],
                row_len=self.info["row_len"],
                stride_bits=self.info["stride_bits"],
                cap=per_dev,
                max_rounds=self.info["max_rounds"],
                uniform_len=self.info["uniform_len"],
                text_mode=self.info["text_mode"],
                text_len=self.info["text_len"],
            )
            smapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
                out_specs=(P(AXIS), P(AXIS), P(AXIS)),
                check_vma=not self.cfg.use_pallas,
            )
            fn = self._fns[per_dev] = jax.jit(smapped)
        return fn

    def refine(self, gidx: np.ndarray) -> np.ndarray:
        """Rank ``gidx`` (int64 global suffix indexes) in exact suffix order."""
        gidx = np.asarray(gidx, np.int64)
        m = gidx.shape[0]
        if m <= 1:
            return gidx.copy()
        per_dev = 1 << max(0, (-(-m // self.d) - 1)).bit_length()
        m_pad = per_dev * self.d
        ih = np.full(m_pad, -1, np.int32)
        il = np.full(m_pad, -1, np.int32)
        ih[:m] = (gidx >> WORD_BITS).astype(np.int32)
        il[:m] = (gidx & (WORD_MOD - 1)).astype(np.int32)
        out_ih, out_il, statmat = self._fn(per_dev)(
            ih, il, self._data, self._lens, self._halo
        )
        out_ih, out_il = np.asarray(out_ih), np.asarray(out_il)
        statmat = np.asarray(statmat)
        if int(statmat[:, 6].sum()) > 0 or int(statmat[:, 5].sum()) > 0:
            raise RuntimeError(
                "device refinement did not converge (unresolved ties/drops)"
            )
        self.calls += 1
        self.requests += int(statmat[:, 1].sum())
        self.request_bytes += int(statmat[:, 2].sum())
        self.response_bytes += int(statmat[:, 3].sum())
        self.rounds += int(statmat[:, 4].max())
        self.peak_records = max(self.peak_records, m)
        n_per = out_ih.shape[0] // self.d
        chunks = []
        for i in range(self.d):
            lo = i * n_per
            cnt = int(statmat[i, 0])
            chunks.append(global_index(out_ih[lo : lo + cnt], out_il[lo : lo + cnt]))
        out = np.concatenate(chunks) if chunks else np.zeros((0,), np.int64)
        assert out.shape[0] == m, (out.shape, m)
        return out


def refine_indices(
    corpus, gidx, cfg: SAConfig = SAConfig(), lengths=None, mesh=None
) -> np.ndarray:
    """One-shot convenience wrapper over :class:`DeviceRefiner`."""
    return DeviceRefiner(corpus, cfg, lengths=lengths, mesh=mesh).refine(gidx)
