"""Model FLOP/s utilisation: (6 N + 12 L heads head_dim seq) FLOP a token
x tokens/s/chip of this run's whole window, over the chip's bf16 peak.
Recomputed operations are not counted."""


def read(h):
    rate = h.counters.get("tok_s_chip")
    if not rate:
        return None
    return 100.0 * rate * h.counters["flops_per_token"] / h.peak.bf16_flops
