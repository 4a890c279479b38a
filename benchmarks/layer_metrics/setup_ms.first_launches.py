"""The summed ``serving.engine.step`` spans of the steps before the
window in which a program record landed: a step program's first launch
is traced and lowered in Python whatever the compile cache holds.  Its
line says the step, the program and the milliseconds of each."""

from benchmarks.lib.setup_ledger import first_launches_ms


def read(h):
    return first_launches_ms(h)
