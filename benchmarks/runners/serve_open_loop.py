"""Independent users: requests arrive on a schedule fixed in the mix,
whatever the system does; each is timed from when it was DUE."""

from . import _serve


def run(h, config, mix, seed, seconds):
    return _serve.run(h, config, mix, seed, seconds, open_loop=True)
