"""LM data pipeline: exact-substring dedup of a token corpus via the
distributed suffix array (the paper's pipeline as an LLM-data substrate).

Plants duplicate spans in a synthetic corpus, finds them with SA+LCP, and
returns a keep mask that drops the later occurrences of each duplicated
span.

    PYTHONPATH=src python examples/dedup_corpus.py
"""
import numpy as np

from repro.config import SAConfig
from repro.data.corpus import synth_token_corpus
from repro.data.dedup import dedup_corpus

VOCAB = 255
tokens, planted = synth_token_corpus(
    6_000, VOCAB, seed=3, dup_fraction=0.08, dup_span=48
)
print(f"corpus: {len(tokens)} tokens, planted {len(planted)} duplicate spans")

cfg = SAConfig(vocab_size=VOCAB, packing="bits")
tokens, keep, stats = dedup_corpus(tokens, min_len=32, cfg=cfg, mode="doubling")
print(f"found spans   : {stats['num_spans']}")
print(f"masked tokens : {stats['masked_tokens']} "
      f"({100 * stats['masked_fraction']:.2f}%)")

# dedup property: no planted pair may survive in full twice (plants can
# overwrite each other, so only still-identical pairs are checkable)
missed = 0
for src, dst, span in planted:
    if np.array_equal(tokens[src : src + span], tokens[dst : dst + span]):
        if keep[src : src + span].all() and keep[dst : dst + span].all():
            missed += 1
assert missed == 0, f"{missed} duplicate pairs fully survived dedup"
print("no duplicate pair survives twice: True")
