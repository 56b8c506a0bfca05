"""The suffix-array system's benchmark: ``python bench/run.py --help``."""
