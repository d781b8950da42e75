"""Causal flash attention, backward: operations and bytes from shapes.

Operations: five products over the causal half where the forward has
two (QK^T again, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q):
10 * b * heads * s*(s+1)/2 * d. That recomputation of QK^T is part of
the algorithm (flash stores no scores), so it is required work here.
Bytes: q, k, v, o, dO read and dq, dk, dv written once, bf16.
"""


def ops_bytes(shapes: dict, calls: int) -> tuple:
    b, s = shapes["batch"], shapes["sequence"]
    hq, hk = shapes["num_attention_heads"], shapes["num_key_value_heads"]
    d = shapes["head_dim"]
    ops = 10.0 * b * hq * (s * (s + 1) / 2) * d
    byts = 2.0 * b * s * d * (4 * hq + 4 * hk) + 8.0 * b * hq * s
    return ops * calls, byts * calls
