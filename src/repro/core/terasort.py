"""TeraSort baseline for SA construction (paper §III).

"Keeping every suffix in place": every suffix is fully materialized as a
fixed-width padded record and the *whole payload* rides the shuffle — the
behaviour whose local-disk analogue breaks the paper's Table III Case 5.
On our mesh the disk pressure becomes shuffle/HBM pressure: record width is
(L+1) tokens + index vs the scheme's constant 16 bytes; the ``Footprint``
of the two implementations gives the paper's ratios
(``examples/sa_build.py``).

Reads mode only (the paper's case); long-text suffixes are unbounded and
cannot be materialized at fixed width — which is itself the point.
"""
from __future__ import annotations

import math
from functools import partial
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
    sample_splitters,
)
from repro.core.pipeline import AXIS, _flat_mesh, plan
from repro.core.store import token_bytes
from repro.core.types import KEY_SENTINEL, Footprint, SAResult, global_index, pack_index


def _suffix_words(l: int, cfg: SAConfig) -> int:
    cpw = cfg.resolved_chars_per_word()
    return -(-(l + 1) // cpw)


def _device_fn(
    reads_l, lengths_l, *, cfg: SAConfig, num_shards, rows_per_shard,
    stride_bits, shuffle_cap, l,
):
    d = num_shards
    me = lax.axis_index(AXIS)
    cpw = cfg.resolved_chars_per_word()
    w = _suffix_words(l, cfg)

    # Map: materialize every suffix fully (padded to W words)
    win = encoding.all_suffix_windows(
        jnp.pad(reads_l, ((0, 0), (0, w * cpw - l))), w * cpw
    )[:, : l + 1]  # (rows, L+1, w*cpw)
    words = encoding.pack_words(win, cfg, n_words=w)  # (rows, L+1, w)
    offs = jnp.arange(l + 1, dtype=jnp.int32)
    valid = offs[None, :] <= lengths_l[:, None]
    rows_ids = jnp.arange(rows_per_shard, dtype=jnp.int32)[:, None] + me * rows_per_shard
    rows_b = jnp.broadcast_to(rows_ids, (rows_per_shard, l + 1))
    ih, il_ = pack_index(rows_b, jnp.broadcast_to(offs[None, :], rows_b.shape), stride_bits)
    rec = jnp.concatenate(
        [jnp.moveaxis(words, -1, 0), ih[None], il_[None]]
    ).reshape(w + 2, rows_per_shard * (l + 1))
    rec = jnp.where(valid.reshape(1, -1), rec, KEY_SENTINEL)
    n_valid_local = jnp.sum(valid).astype(jnp.int32)

    # Sample/partition on the first two words (TeraSort's 10-byte key analogue)
    s_hi, s_lo = sample_splitters(rec[0], rec[1], cfg.samples_per_shard, AXIS)
    bucket = lex_bucket(rec[0], rec[1], s_hi, s_lo)

    # Shuffle the full payload (the baseline's sin)
    buf, _, drop = bucket_scatter(rec, bucket, d, shuffle_cap, KEY_SENTINEL)
    recv = exchange(buf, AXIS).reshape(w + 2, d * shuffle_cap)

    cols = tuple(recv)
    out = lax.sort(cols, num_keys=w + 2)
    ih, il_ = out[w], out[w + 1]
    count = jnp.sum(out[0] != KEY_SENTINEL).astype(jnp.int32)
    statvec = jnp.stack([count, n_valid_local, drop])
    return ih, il_, statvec[None, :]


def build_suffix_array_terasort(
    corpus, lengths=None, cfg: SAConfig = SAConfig(), mesh: Optional[Mesh] = None,
) -> SAResult:
    corpus = np.asarray(corpus, np.int32)
    assert corpus.ndim == 2, "TeraSort baseline supports read-set mode only"
    mesh = _flat_mesh(mesh)
    d = mesh.devices.size
    info = plan(corpus.shape, cfg, d, lengths)
    from repro.core.pipeline import _exact_shuffle_cap, _shard_inputs

    data, lens, halo = _shard_inputs(corpus, lengths, cfg, d, info)
    sharding = NamedSharding(mesh, P(AXIS))
    data = jax.device_put(data, sharding)
    lens = jax.device_put(lens, sharding)
    halo = jax.device_put(halo, sharding)
    shuffle_cap = info["shuffle_cap"]
    if cfg.adaptive:
        shuffle_cap = _exact_shuffle_cap(corpus.shape, cfg, mesh, data, lens, halo)

    l = corpus.shape[1]
    fn = partial(
        _device_fn, cfg=cfg, num_shards=d, rows_per_shard=info["rows_per_shard"],
        stride_bits=info["stride_bits"], shuffle_cap=shuffle_cap, l=l,
    )
    smapped = jax.shard_map(
        fn, mesh=mesh, in_specs=(P(AXIS), P(AXIS)), out_specs=(P(AXIS), P(AXIS), P(AXIS)),
    )
    ih, il_, statmat = jax.jit(smapped)(data, lens)
    ih, il_, statmat = np.asarray(ih), np.asarray(il_), np.asarray(statmat)

    per_dev = ih.shape[0] // d
    chunks = []
    for i in range(d):
        lo = i * per_dev
        cnt = int(statmat[i, 0])
        chunks.append(global_index(ih[lo : lo + cnt], il_[lo : lo + cnt]))
    sa = np.concatenate(chunks)

    tb = token_bytes(cfg.vocab_size)
    n_suffix = int(statmat[:, 1].sum())
    suffix_bytes = (l + 1) * tb + 8  # materialized payload + index
    fp = Footprint(
        input=int(corpus.size) * tb,
        store_put=0,  # no in-memory store: every suffix kept in place
        shuffle=n_suffix * suffix_bytes,
        fetch_request=0,
        fetch_response=0,
        materialized=n_suffix * suffix_bytes,
        output=n_suffix * 8,
        rounds=0,
        dropped=int(statmat[:, 2].sum()),
    )
    stats = {
        "num_suffixes": n_suffix,
        "emitted": int(sa.shape[0]),
        "record_bytes": suffix_bytes,
        "dropped": fp.dropped,
    }
    return SAResult(suffix_array=sa, footprint=fp, stats=stats)
