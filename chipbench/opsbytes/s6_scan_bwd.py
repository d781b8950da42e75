"""The selective scan (Mamba-1, S6), backward: operations and bytes from
shapes.

Operations: the forward's recurrence made again inside a chunk, then the
cotangent recurrence with its four products a state entry: three times
the forward's 4 * b * s * d_inner * n. Bytes, each array once: ``u``
(bf16), ``delta`` (float32), ``B``, ``C`` (bf16), the float32 states
before each chunk and the cotangent of ``y`` (bf16) in; the cotangents of
``u`` (bf16), ``delta`` (float32), ``B``, ``C`` (bf16) and ``A``
(float32, ``[d_inner, n]``) out. The kernel as it stands reads and writes
float32 throughout and writes a partial ``dB`` and ``dC`` for every block
of channels: the least is counted, not what it moves.
"""

from chipbench.opsbytes.s6_scan_fwd import sizes


def ops_bytes(shapes: dict, calls: int) -> tuple:
    b, s, d, n, chunk = sizes(shapes)
    ops = 3 * 4.0 * b * s * d * n
    byts = b * s * (d * (2 + 4 + 2) + 2 * n * 2) \
        + 4.0 * b * (s // chunk) * n * d \
        + b * s * (d * (2 + 4) + 2 * n * 2) + 4.0 * d * n
    return ops * calls, byts * calls
