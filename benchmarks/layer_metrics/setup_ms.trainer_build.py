"""Milliseconds under the program's ``trainer.build`` span
(``build_llama_pretrain_step``, before the window); its line says each
section — model, state, step, plan — and under the plan the floor
program and each compiled try."""

from benchmarks.lib.setup_ledger import span_ms


def read(h):
    return span_ms(h, "trainer.build")
