"""Device ms a step of the snapshots: everything the program runs under
``tail_snapshot`` (the copy of a page's last two rows of ``B * z`` into
the page's entry of the plane, every conv block), mean over the traced
steps (``lib/lfm2_spans``)."""

from benchmarks.lib import lfm2_spans as fs


def read(h):
    return fs.ms_a_step(h, ("tail_snapshot",))
