"""Operations and bytes of one call of the Pallas flash-attention forward.

The call's operands are q ``(B, H, S, Dp)`` and k, v ``(B, K, S, Dp)``,
with the head dim padded to a multiple of 128.  The work is counted at the
configuration's published head dim, not the padded one, and for causal
attention only the ``S (S + 1) / 2`` query-key pairs a causal mask keeps:
``QK^T`` and ``PV`` are 2 operations per pair per head dim each.  Bytes:
q, k, v read once and the output written once, at the published dim.
"""
import hlo


def cost(call, model):
    q, k = call["operands"][0], call["operands"][1]
    b, h, s, _ = q["shape"]
    kv = k["shape"][1]
    d = model["head_dim"]
    pairs = s * (s + 1) // 2
    flops = 4 * b * h * pairs * d
    width = hlo.DTYPE_BYTES[q["dtype"]]
    nbytes = width * b * s * d * (2 * h + 2 * kv)
    return flops, nbytes
