"""Median duration, in ms, of the ``serve.launch`` span (the ``search_mixed``
call: flags, entry acquisition and the dispatch of the beam program) over
the batches whose ``serve.pack`` starts in the traced window."""
import spans


def read(run):
    return spans.search_launch_ms(run.trace)
