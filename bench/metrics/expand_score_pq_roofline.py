"""``expand_score_pq``'s share of its roofline, in percent: the least time
of its launches (from their call shapes, ``bench/work/expand_score_pq.py``)
over the device time they took."""


def read(run):
    return run.roofline("expand_score_pq")
