"""Causal flash attention, forward: operations and bytes from shapes.

Operations: QK^T and PV over the causal half, 4 * b * heads * s*(s+1)/2
* d. Bytes: q and the output once, k and v once (each KV head is read
for its whole group from fast memory in the ideal case), bf16; the
log-sum-exp row in float32.
"""


def ops_bytes(shapes: dict, calls: int) -> tuple:
    b, s = shapes["batch"], shapes["sequence"]
    hq, hk = shapes["num_attention_heads"], shapes["num_key_value_heads"]
    d = shapes["head_dim"]
    ops = 4.0 * b * hq * (s * (s + 1) / 2) * d
    byts = 2.0 * b * s * d * (2 * hq + 2 * hk) + 4.0 * b * hq * s
    return ops * calls, byts * calls
