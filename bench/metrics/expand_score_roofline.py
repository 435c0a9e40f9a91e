"""``expand_score``'s share of its roofline, in percent: the least time of
its launches (from their call shapes, ``bench/work/expand_score.py``) over
the device time they took."""


def read(run):
    return run.roofline("expand_score")
