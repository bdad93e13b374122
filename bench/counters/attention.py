"""Operations and bytes attention needs.

Causal attention counts the pairs (query, key) it must score: query i of
q_len sees keys 0 .. i + (kv_len - q_len), bottom-right aligned, so the
count is the lower triangle and never the full square.  Each pair costs
2 * head_dim for the scores and 2 * head_dim for the weighted values.
Bytes are queries and outputs at the query-head count and keys and values
at the key-value-head count, each read or written once.  A decode step
passes kv_len = pos + 1, the keys it must read, not the allocated cache.
"""


def count(op: dict, itemsize: int) -> tuple:
    B, H, Hkv = op["batch"], op["heads"], op["kv_heads"]
    Sq, Skv, D = op["q_len"], op["kv_len"], op["head_dim"]
    if op["causal"]:
        pairs = Sq * (Skv - Sq) + Sq * (Sq + 1) // 2
    else:
        pairs = Sq * Skv
    flops = 4.0 * B * H * pairs * D
    nbytes = itemsize * (2 * B * H * Sq * D + 2 * B * Hkv * Skv * D)
    return flops, float(nbytes)
