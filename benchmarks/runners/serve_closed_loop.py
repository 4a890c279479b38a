"""Callers that wait: ``clients`` of them, each sending its next request
when its last one completes.  The window ends with requests in flight;
they are cut, not failed."""

from . import _serve


def run(h, config, mix, seed, seconds):
    return _serve.run(h, config, mix, seed, seconds, open_loop=False)
