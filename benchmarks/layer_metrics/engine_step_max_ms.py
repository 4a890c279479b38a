"""The longest ``serving.engine.step`` of the window.  The reader says
that step's whole record, phases and counts, so that a stall names its
phase; and, over the traced steps, how the six phases close against the
benchmark's own span around ``step()``."""

from benchmarks.lib.harness import say
from benchmarks.lib.program_spans import (in_window, phase_ms_of,
                                          say_step_books, window)


def read(h):
    w = window(h)
    if w is None or not in_window(w):
        return None
    seen, rec = max(in_window(w), key=lambda sr: phase_ms_of(sr[1])["step"])
    ms = phase_ms_of(rec)
    counts = {k: v for k, v in rec.items()
              if k not in ("phases", "start_ns", "end_ns", "name")}
    say(f"longest step of {len(in_window(w))}: {ms['step']:.3f} ms at "
        f"{seen['t']:.2f}s{' (traced)' if seen['traced'] else ''}; phases "
        f"ms {({k: round(v, 3) for k, v in ms.items() if k != 'step'})}; "
        f"{counts}")
    say_step_books(h)
    return ms["step"]
