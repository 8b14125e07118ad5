"""Roofline share of the `quant_int8` Pallas kernel (the int8 quantizer):
the bytes it must move in the traced steps over its device time x the
chip's HBM bandwidth, in percent (`bench/train_work.roofline`)."""
from bench import train_work


def read(run):
    return train_work.roofline(run, "quant", "quant_int8")
