"""The fused paged decode kernel: bytes from shapes (it is bound by
bytes: a multiply-add per byte read).

One call is one decode step of one layer over every slot. It reads each
active slot's K and V pages up to the slot's length and the new
token's q, k, v, and writes the output and the appended row (copied from
``benchmarks/kernelbench.py:decode_hbm_bytes``, paged mode, with the
sublane tile the append writes back: 16 rows of bf16). The lengths are
the run's own: ``ctx_tokens_mean`` is the mean, over samples of the
engine's ``seq_lens`` taken while slots were active, of the tokens in
the cache summed over the active slots.
"""

APPEND_TILE_ROWS = 16


def ops_bytes(shapes: dict, calls: int) -> tuple:
    kvh, d = shapes["num_key_value_heads"], shapes["head_dim"]
    hq = shapes["num_attention_heads"]
    ctx, active = shapes["ctx_tokens_mean"], shapes["slots_active_mean"]
    item = 2  # bf16
    kv_read = 2.0 * ctx * kvh * d * item
    qkv_o = active * (2 * hq + 2 * kvh) * d * item
    append = 2.0 * active * kvh * APPEND_TILE_ROWS * d * item * 2  # r + w
    ops = 4.0 * ctx * hq * d
    return ops * calls, (kv_read + qkv_o + append) * calls
