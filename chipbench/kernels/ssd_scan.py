"""Operations and bytes of one call of the Pallas SSD (Mamba-2) chunked
scan forward.

Operands: x ``(b, h, s, p)``, dt ``(b, h, s, 1)``, A ``(h,)``, B and C
``(b, g, s, n)``.  The chunked algorithm (arXiv:2405.21060, section 6),
with chunk ``c`` from the configuration, needs per position: the ``C_t .
B_s`` scores against the ``(c + 1) / 2`` earlier positions of its chunk (per
group), the weighted sum of their inputs (per head), its share of the
chunk's state and the state's contribution to its output (``p n`` each,
per head); 2 operations per multiply-add.  Elementwise decays are not
counted.  Bytes: every operand read once and the output written once.
"""
import hlo


def cost(call, model):
    x, _dt, _a, bm, _c = call["operands"]
    b, h, s, p = x["shape"]
    g, n = bm["shape"][1], bm["shape"][3]
    c = model["chunk_size"]
    pairs = (c + 1) / 2
    flops = 2 * b * s * (g * pairs * n + h * pairs * p + 2 * h * p * n)
    nbytes = sum(hlo.nbytes(t) for t in call["operands"]) \
        + sum(hlo.nbytes(t) for t in call["result"])
    return flops, nbytes
