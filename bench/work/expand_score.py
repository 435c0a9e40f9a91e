"""Useful work of one ``expand_score`` launch, from its call shapes.

The kernel scores ``C`` candidate rows for each of ``B`` queries of width
``d`` (f32).  Useful bytes are the gathered rows (``B·C·d·4``), the
candidate ids and the distances out (``B·C·4`` each) and the queries
(``B·d·4``); not the whole HBM tiles a kernel may move to reach a row.
Operations: a subtract, a multiply and an add for each element of each row.
"""


def work(shapes):
    B, C = shapes["out"][-2:]
    d = shapes["args"][1][-1]
    ops = 3 * B * C * d
    nbytes = 4 * (B * C * d + 2 * B * C + B * d)
    return ops, nbytes
