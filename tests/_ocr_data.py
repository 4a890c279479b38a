"""Synthetic OCR data shared by the OCR quality gates: 64x64 det scenes
(textured boxes, digit lines) with their DB targets, and 32x64 rec strips.
Every generator draws from the `rng` it is given and from nothing else."""

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


def line(rng):
    """Four digits at a pitch of 16 as a [20, 64] strip, and their label."""
    strip = np.zeros((20, 64), np.float32)
    label = rng.randint(0, 10, 4)
    for i, d in enumerate(label):
        strip[:, i * 16 + 2:i * 16 + 14] = np.kron(
            glyph(int(d)), np.ones((4, 4), np.float32))
    return strip, label


def strip_image(strip, off):
    """The [1, 32, 64] rec input with `strip` starting at row `off`."""
    im = np.zeros((1, 32, 64), np.float32)
    im[0, off:off + 20] = strip
    return im


def rec_batch(rng, B):
    """B line strips 2-9 rows down (the det crop centres a line only
    approximately: it lands 2-9 rows down, mostly 6-7, so rec trains
    offset-robust) and their CTC labels (0 is the blank)."""
    imgs, labs = [], []
    for _ in range(B):
        strip, lb = line(rng)
        imgs.append(strip_image(strip, rng.randint(2, 10)))
        labs.append(lb + 1)
    return np.stack(imgs), np.stack(labs).astype(np.int32)


def _add_box(tgt, x0, y0, bw, bh):
    """DB targets of one box: shrink map (inset 2), border-band threshold
    map and mask (2 either side of the edge)."""
    shrink, tmap, tmask = tgt
    inner = np.s_[y0 + 2:y0 + bh - 2, x0 + 2:x0 + bw - 2]
    shrink[inner] = 1.0
    band = np.zeros_like(shrink)
    band[max(0, y0 - 2):y0 + bh + 2, max(0, x0 - 2):x0 + bw + 2] = 1.0
    band[inner] = 0.0
    np.maximum(tmap, band * 0.55, out=tmap)
    np.maximum(tmask, band, out=tmask)


def box_scene(rng):
    """1-2 textured (checkerboard) rectangles on a noisy 64x64 scene.
    Returns img [1, 64, 64], the three DB targets and the GT boxes."""
    img = rng.uniform(0.0, 0.15, (1, 64, 64)).astype(np.float32)
    tgt = np.zeros((3, 64, 64), np.float32)
    boxes = []
    for _ in range(rng.randint(1, 3)):
        for _try in range(20):
            bh, bw = rng.randint(12, 22), rng.randint(14, 26)
            y0 = rng.randint(2, 64 - bh - 2)
            x0 = rng.randint(2, 64 - bw - 2)
            if all(x0 + bw + 4 < px0 or px1 + 4 < x0
                   or y0 + bh + 4 < py0 or py1 + 4 < y0
                   for (px0, py0, px1, py1) in boxes):
                break
        else:
            continue
        yy, xx = np.mgrid[0:bh, 0:bw]
        img[0, y0:y0 + bh, x0:x0 + bw] = \
            0.55 + 0.45 * (((yy // 2) + (xx // 2)) % 2)
        _add_box(tgt, x0, y0, bw, bh)
        boxes.append((x0, y0, x0 + bw - 1, y0 + bh - 1))
    return img, tgt, boxes


def line_scene(rng):
    """A digit line at a random vertical offset on a black 64x64 scene.
    Returns img [1, 64, 64], the three DB targets and the label."""
    strip, label = line(rng)
    dy = rng.randint(2, 42)
    img = np.zeros((1, 64, 64), np.float32)
    img[0, dy:dy + 20] = strip
    tgt = np.zeros((3, 64, 64), np.float32)
    _add_box(tgt, 2, dy, 60, 20)
    return img, tgt, label


def det_batch(rng, n_box, n_line):
    """`n_box` box scenes then `n_line` line scenes as one batch: imgs
    [B, 1, 64, 64], targets [B, 3, 64, 64], and each scene's boxes/label."""
    scenes = ([box_scene(rng) for _ in range(n_box)]
              + [line_scene(rng) for _ in range(n_line)])
    imgs, tgts, truth = zip(*scenes)
    return np.stack(imgs), np.stack(tgts), list(truth)
