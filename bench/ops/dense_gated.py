"""The work one step of a dense decoder with a gated MLP needs, from the
configuration and the traffic alone: q/k/v/o projections, gate/up/down,
the output head and attention, each with how many times a step runs it.
Each op's operations and bytes are counted by ``bench/counters/<kind>.py``.
"""


def step_ops(run: dict, phase: str, batch: int, prompt_len: int,
             pos: int = 0) -> list:
    """Ops of one prefill step (``batch`` prompts of ``prompt_len``) or one
    decode step writing position ``pos``."""
    L, d, f = run["n_layers"], run["d_model"], run["d_ff"]
    H, Hkv, D, V = (run["n_heads"], run["n_kv_heads"], run["head_dim"],
                    run["vocab_size"])
    if phase == "prefill":
        M, q_len, kv_len, causal = batch * prompt_len, prompt_len, \
            prompt_len, True
    else:
        M, q_len, kv_len, causal = batch, 1, pos + 1, False

    def mm(name, m, n, k, mult):
        return {"kind": "matmul", "name": name, "m": m, "n": n, "k": k,
                "mult": mult}

    return [
        mm("attn.q", M, H * D, d, L), mm("attn.k", M, Hkv * D, d, L),
        mm("attn.v", M, Hkv * D, d, L), mm("attn.o", M, d, H * D, L),
        mm("mlp.gate", M, f, d, L), mm("mlp.up", M, f, d, L),
        mm("mlp.down", M, d, f, L),
        # prefill scores the last position only
        mm("lm_head", batch, V, d, 1),
        {"kind": "attention", "name": "attn.core", "batch": batch,
         "heads": H, "kv_heads": Hkv, "q_len": q_len, "kv_len": kv_len,
         "head_dim": D, "causal": causal, "mult": L},
    ]
