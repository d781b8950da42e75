"""The selective scan (Mamba-1, S6), forward: operations and bytes from
shapes.

Operations: per token, channel and state column the state's update and
the read-out, two multiply-adds (4 * b * s * d_inner * n); they are
vector work, counted against the matrix unit's peak all the same, and
bound nothing. Bytes, each array once: ``u`` in (bf16), ``delta`` in
(float32), ``B`` and ``C`` in (bf16), ``y`` out (bf16), and the float32
state before each chunk out, ``[b, s / chunk, n, d_inner]``, which the
backward reads. The kernel as it stands is handed ``u``, ``B`` and ``C``
cast to float32 and writes ``y`` in float32: the least is counted, not
what it moves.
"""


def sizes(shapes: dict) -> tuple:
    return (shapes["batch"], shapes["sequence"],
            shapes["mamba_expand"] * shapes["hidden_size"],
            shapes["mamba_d_state"], shapes["scan_chunk"])


def ops_bytes(shapes: dict, calls: int) -> tuple:
    b, s, d, n, chunk = sizes(shapes)
    ops = 4.0 * b * s * d * n
    byts = b * s * (d * (2 + 4 + 2) + 2 * n * 2) \
        + 4.0 * b * (s // chunk) * n * d
    return ops * calls, byts * calls
