"""Roofline share of the `dequant_int8` Pallas kernel (the int8 dequantizer):
the bytes it must move in the traced steps over its device time x the
chip's HBM bandwidth, in percent (`bench/train_work.roofline`)."""
from bench import train_work


def read(run):
    return train_work.roofline(run, "dequant", "dequant_int8")
