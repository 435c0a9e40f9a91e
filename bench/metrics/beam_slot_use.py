"""Expansions the beam loop did over the expansion slots it ran, in percent,
over the batches resolved in the traced window: the sum of ``steps_sum``
over the sum of ``iters * width * bucket`` of their ``serve.resolve``
spans.  The loop runs every row of a batch until its slowest row is done,
so this is the share of its slots that expanded a node."""
import spans


def read(run):
    return spans.beam_slot_use(run.trace)
