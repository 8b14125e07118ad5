"""Roofline share of the `gf_matmul` Pallas kernel (RS encode and decode):
the bytes it must move in the traced steps over its device time x the
chip's HBM bandwidth, in percent (`bench/train_work.roofline`)."""
from bench import train_work


def read(run):
    return train_work.roofline(run, "rs", "gf_matmul")
