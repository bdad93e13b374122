"""Operations and bytes latent attention (MLA) needs.

Decode (absorbed): each of ``heads`` query heads scores the ``kv_len``
cached positions against the latent (``latent``) and the shared rope key
(``rope``), 2 * (latent + rope) a pair, and sums the latent weighted by
the probabilities, 2 * latent a pair; the cache is read once,
kv_len * (latent + rope) elements a request, beside the absorbed queries
and the latent contexts.

Prefill (expanded): causal pairs, query i of q_len seeing keys 0 .. i +
(kv_len - q_len), each 2 * qk_dim for the score and 2 * v_dim for the
value; queries and keys at ``qk_dim``, values and outputs at ``v_dim``,
each read or written once, for every head.
"""


def count(op: dict, itemsize: int) -> tuple:
    B, H, Sq, Skv = op["batch"], op["heads"], op["q_len"], op["kv_len"]
    if op["phase"] == "decode":
        r, dr = op["latent"], op["rope"]
        flops = 2.0 * B * H * Skv * ((r + dr) + r)
        nbytes = itemsize * (B * Skv * (r + dr) + B * H * (r + dr)
                             + B * H * r)
        return flops, float(nbytes)
    dq, dv = op["qk_dim"], op["v_dim"]
    pairs = Sq * (Skv - Sq) + Sq * (Sq + 1) // 2
    flops = 2.0 * B * H * pairs * (dq + dv)
    nbytes = itemsize * B * H * (Sq * dq + Skv * dq + Skv * dv + Sq * dv)
    return flops, float(nbytes)
