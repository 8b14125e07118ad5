"""Compilations inside the measured window of a training cell:
`window_compiles`'s count (JAX's compile-cache events between the
window's first call and its last), named apart because it moves
`tokens_per_s`.  Should read 0."""
from bench.harness import load_module


def read(run):
    return load_module("metrics", "window_compiles").read(run)
