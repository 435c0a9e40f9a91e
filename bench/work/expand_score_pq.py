"""Useful work of one ``expand_score_pq`` launch, from its call shapes.

The kernel scores ``C`` candidates for each of ``B`` queries from their
``m`` one-byte codes and each query's ``(256, m)`` f32 distance table.
Useful bytes are the codes (``B·C·m``), the tables read once per query
(``B·256·m·4``), and the ids and distances (``B·C·4`` each).  Operations:
one table lookup and one add for each code.
"""


def work(shapes):
    B, C = shapes["out"][-2:]
    m = shapes["args"][1][-1]
    ops = 2 * B * C * m
    nbytes = B * C * m + 4 * (B * 256 * m + 2 * B * C)
    return ops, nbytes
