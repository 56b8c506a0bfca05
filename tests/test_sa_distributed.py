"""Multi-device (8 fake CPU devices) correctness of the distributed pipelines.

Runs in subprocesses because device count must be fixed before jax init.
"""
import pytest


@pytest.mark.slow
def test_scheme_8dev(run_multidev):
    out = run_multidev(
        """
        import numpy as np
        from repro.config import SAConfig
        from repro.core.pipeline import build_suffix_array
        from repro.core.oracle import naive_sa_reads, doubling_sa_text

        rng = np.random.default_rng(1)
        reads = rng.integers(1, 5, size=(101, 17)).astype(np.int32)
        cfg = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)
        res = build_suffix_array(reads, cfg=cfg)
        assert np.array_equal(res.suffix_array, naive_sa_reads(reads)), "reads"
        assert res.stats["dropped"] == 0

        text = rng.integers(1, 5, size=(1000,)).astype(np.int32)
        cfg = SAConfig(vocab_size=4, chars_per_word=3, key_words=2)
        res = build_suffix_array(text, cfg=cfg)
        assert np.array_equal(res.suffix_array, doubling_sa_text(text)), "text"
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_scheme_8dev_adversarial(run_multidev):
    out = run_multidev(
        """
        import numpy as np
        from repro.config import SAConfig
        from repro.core.pipeline import build_suffix_array
        from repro.core.oracle import naive_sa_text, naive_sa_reads

        cfg = SAConfig(vocab_size=4, chars_per_word=3, key_words=2)
        text = np.tile(np.array([1, 2], np.int32), 150)
        res = build_suffix_array(text, cfg=cfg)
        assert np.array_equal(res.suffix_array, naive_sa_text(text)), "repeat"

        rng = np.random.default_rng(2)
        lens = rng.integers(0, 12, size=(37,)).astype(np.int32)
        reads = np.zeros((37, 12), np.int32)
        for i, n in enumerate(lens):
            reads[i, :n] = rng.integers(1, 5, size=(n,))
        cfg = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)
        res = build_suffix_array(reads, lengths=lens, cfg=cfg)
        assert np.array_equal(res.suffix_array, naive_sa_reads(reads, lens)), "varlen"
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_terasort_and_doubling_8dev(run_multidev):
    out = run_multidev(
        """
        import numpy as np
        from repro.config import SAConfig
        from repro.core.terasort import build_suffix_array_terasort
        from repro.core.prefix_doubling import build_suffix_array_doubling
        from repro.core.oracle import naive_sa_reads, doubling_sa_text, naive_sa_text

        rng = np.random.default_rng(3)
        reads = rng.integers(1, 5, size=(101, 17)).astype(np.int32)
        cfg = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)
        res = build_suffix_array_terasort(reads, cfg=cfg)
        assert np.array_equal(res.suffix_array, naive_sa_reads(reads)), "terasort"

        cfg = SAConfig(vocab_size=4, chars_per_word=3, key_words=2)
        text = rng.integers(1, 5, size=(1000,)).astype(np.int32)
        res = build_suffix_array_doubling(text, cfg=cfg)
        assert np.array_equal(res.suffix_array, doubling_sa_text(text)), "dbl rnd"
        assert res.stats["dropped"] == 0

        text = np.ones(257, np.int32)
        res = build_suffix_array_doubling(text, cfg=cfg)
        assert np.array_equal(res.suffix_array, naive_sa_text(text)), "dbl same"
        print("OK")
        """
    )
    assert "OK" in out


@pytest.mark.slow
def test_store_primitives_8dev(run_multidev):
    out = run_multidev(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.store import StoreSpec, mget_scalar, scatter_update

        mesh = Mesh(np.array(jax.devices()), ("sa",))
        d, rows = 8, 16
        spec = StoreSpec(axis="sa", num_shards=d, rows_per_shard=rows,
                         row_len=1, request_capacity=8)

        def f(vals, pos):
            active = pos >= 0
            got, dropped = mget_scalar(vals, pos, active, spec, fill=-1)
            return got, dropped[None]

        vals = np.arange(d * rows, dtype=np.int32)
        rng = np.random.default_rng(0)
        pos = rng.permutation(d * rows).astype(np.int32)
        sm = jax.shard_map(f, mesh=mesh, in_specs=(P("sa"), P("sa")),
                       out_specs=(P("sa"), P("sa")))
        got, dropped = jax.jit(sm)(vals, pos)
        assert np.array_equal(np.asarray(got), vals[pos]), "mget"
        assert np.asarray(dropped).sum() == 0

        def g(vals, pos, newv):
            active = pos >= 0
            out, dropped = scatter_update(vals, pos, newv, active, spec)
            return out, dropped[None]

        newv = (np.arange(d * rows) * 7 % 1000).astype(np.int32)
        sm2 = jax.shard_map(g, mesh=mesh, in_specs=(P("sa"),) * 3,
                        out_specs=(P("sa"), P("sa")))
        out, dropped = jax.jit(sm2)(np.zeros(d * rows, np.int32), pos, newv)
        expect = np.zeros(d * rows, np.int32)
        expect[pos] = newv
        assert np.array_equal(np.asarray(out), expect), "scatter"
        print("OK")
        """
    )
    assert "OK" in out


STATIC_SLACKS = (2.0, 0.5)


@pytest.fixture(scope="module")
def static_builds(run_multidev, tmp_path_factory):
    """One 4-device subprocess: ``SuffixArrayIndex.build`` under the static
    shuffle capacity (``adaptive=False``) at each of ``STATIC_SLACKS``,
    published to an index directory and reopened; per slack, what the
    index holds against the plain reference, its shuffle counters and who
    named its LCP."""
    import json

    root = tmp_path_factory.mktemp("static")
    out = run_multidev(
        f"""
        import json
        import numpy as np, jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.config import SAConfig
        from repro.core import pipeline
        from repro.core.oracle import lcp_kasai, naive_sa_reads
        from repro.data.corpus import synth_dna_reads
        from repro.serve.sa_engine import SuffixArrayIndex

        reads = synth_dna_reads(64, 50, seed=7)  # 16x coverage
        r, l = reads.shape
        stride = int(np.ceil(np.log2(l + 1)))
        sa_ref = naive_sa_reads(reads, stride_bits=stride)
        # the read set as one text: read i ends in a separator below every
        # token and below read j's for i < j, so ties order by read as the
        # global index does, and no separator matches another
        text = np.concatenate(
            [reads, (np.arange(r) - r)[:, None]], axis=1).ravel()
        pos = (sa_ref >> stride) * (l + 1) + (sa_ref & ((1 << stride) - 1))
        lcp_ref = lcp_kasai(text, pos)
        mesh = Mesh(np.array(jax.devices()), ("sa",))
        out = {{}}
        for slack in {STATIC_SLACKS!r}:
            cfg = SAConfig(vocab_size=4, adaptive=False, shuffle_slack=slack)
            d = f"{root}/slack{{slack}}"
            SuffixArrayIndex.build(reads, cfg=cfg, index_dir=d,
                                   mesh=mesh).close()
            with SuffixArrayIndex.open(d) as idx:
                info = pipeline.plan(reads.shape, cfg, 4)
                sh = NamedSharding(mesh, P("sa"))
                data, lens, halo = (jax.device_put(a, sh) for a in
                                    pipeline._shard_inputs(reads, None, cfg,
                                                           4, info))
                out[str(slack)] = dict(
                    sa_exact=bool(np.array_equal(idx.sa, sa_ref)),
                    lcp_exact=bool(np.array_equal(idx.lcp, lcp_ref)),
                    sa_len=int(idx.sa.shape[0]),
                    static_cap=info["shuffle_cap"],
                    exact_cap=pipeline._exact_shuffle_cap(
                        reads.shape, cfg, mesh, data, lens, halo),
                    **{{k: idx.build_stats[k] for k in (
                        "num_suffixes", "dropped", "unresolved",
                        "shuffle_slots", "shuffle_records",
                        "shuffle_dropped", "shuffle_rebuilds",
                        "lcp_device_pairs", "lcp_host_pairs")}})
        print("RESULT " + json.dumps(out))
        """,
        devices=4, timeout=300)
    line = next(s for s in out.splitlines() if s.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("slack", STATIC_SLACKS)
def test_static_capacity_publishes_exact_index(static_builds, slack):
    """The static posture publishes the reference's SA and LCP whether its
    shuffle fits (slack 2.0) or overflows and is rebuilt (slack 0.5)."""
    got = static_builds[str(slack)]
    assert got["sa_exact"] and got["lcp_exact"], got
    assert got["sa_len"] == got["num_suffixes"] == 64 * 51
    assert got["dropped"] == 0 and got["unresolved"] == 0
    if slack >= 1.0:
        assert got["shuffle_dropped"] == 0 and got["shuffle_rebuilds"] == 0
    else:
        assert got["shuffle_dropped"] > 0 and got["shuffle_rebuilds"] == 1


def test_shuffle_counters_reach_build_stats(static_builds):
    """``shuffle_slots`` counts d*d*cap a pipeline run (both runs where the
    static capacity overflowed), ``shuffle_records`` every suffix once;
    both read back from the reopened index's manifest."""
    fits, rebuilt = static_builds["2.0"], static_builds["0.5"]
    assert fits["static_cap"] == 64 // 4 * 51 * 2 // 4
    assert fits["shuffle_slots"] == 4 * 4 * fits["static_cap"]
    assert rebuilt["shuffle_slots"] == 4 * 4 * (rebuilt["static_cap"]
                                                + rebuilt["exact_cap"])
    for got in (fits, rebuilt):
        assert got["shuffle_records"] == got["num_suffixes"]


@pytest.mark.parametrize("slack", STATIC_SLACKS)
def test_static_build_lcp_named_on_device(static_builds, slack):
    """The four devices name every LCP pair but the three that cross a
    device boundary, whether the shuffle fits or is rebuilt; the host
    fetches those three."""
    got = static_builds[str(slack)]
    assert got["lcp_host_pairs"] == 3
    assert got["lcp_device_pairs"] + 3 == got["num_suffixes"] - 1
