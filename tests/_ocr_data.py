"""Synthetic OCR data shared by the OCR quality gates."""

import numpy as np


def glyph(d):
    """5x3 bitmap font for digits 0-9."""
    F = {
        0: "111101101101111", 1: "010110010010111",
        2: "111001111100111", 3: "111001111001111",
        4: "101101111001001", 5: "111100111001111",
        6: "111100111101111", 7: "111001001001001",
        8: "111101111101111", 9: "111101111001111",
    }
    return np.asarray([int(c) for c in F[d]], np.float32).reshape(5, 3)
