"""Median duration, in ms, of the serve runtime's ``serve.pack`` span (expiry,
stack and pad of one batch) over the batches that start in the traced
window."""
import spans


def read(run):
    return spans.serve_pack_ms(run.trace)
