"""Device ms a step of the transfer rule — argmax, confidence (a softmax
over the vocabulary a block row), top-k over the block's masked rows and
the block update: everything the program runs under ``unmask`` — mean
over the traced steps (``lib/sdar_spans``)."""

from benchmarks.lib import sdar_spans as ds


def read(h):
    return ds.ms_a_step(h, ("unmask",))
