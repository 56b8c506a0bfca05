"""Query serving over a built or reopened suffix-array index.

Nothing is imported eagerly: ``repro.serve.sa_engine`` holds
``SuffixArrayIndex`` and ``ShardedSAEngine``.
"""
