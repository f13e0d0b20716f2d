"""A benchmark of DiveBatch training and paged serving on a TPU; see run.py."""
