"""The work one step of a latent-attention MoE decoder (DeepSeek-V2) needs,
from the configuration and the traffic alone, each op with how many times
a step runs it.  Each op's operations and bytes are counted by
``bench/counters/<kind>.py``.

Per layer: the MLA projections (q down and up, the joint latent and rope
key, out); in prefill the latent's expansion to keys and values and the
expanded causal attention, in decode the absorbed form (the query through
W_UK, the attention over the latent cache, the context through W_UV).
The leading dense layers add a gated MLP; each MoE layer the router, the
shared experts and the held experts' grouped products.  The held experts
expect ``batch * top_k * held / n_experts`` rows a step, and every held
expert's weights are read once.  The router runs in float32 but is counted
at the configuration's item size (3.3 MB a layer at DeepSeek-V2's widths,
counted as half that).
"""


def step_ops(run: dict, phase: str, batch: int, prompt_len: int,
             pos: int = 0) -> list:
    """Ops of one prefill step (``batch`` prompts of ``prompt_len``) or one
    decode step writing position ``pos``."""
    L, Ld, d = run["n_layers"], run["n_dense_layers"], run["d_model"]
    Lm = L - Ld
    H, V = run["n_heads"], run["vocab_size"]
    r, rq = run["kv_lora_rank"], run["q_lora_rank"]
    dn, dr, dv = run["qk_nope_dim"], run["qk_rope_dim"], run["v_head_dim"]
    E, held, K = run["n_experts"], run["n_held_experts"], run["moe_top_k"]
    f, fe = run["d_ff"], run["moe_d_ff"]
    fs = run["n_shared_experts"] * fe
    M = batch * prompt_len if phase == "prefill" else batch

    def mm(name, m, n, k, mult):
        return {"kind": "matmul", "name": name, "m": m, "n": n, "k": k,
                "mult": mult}

    def gmm(name, n, k):
        return {"kind": "grouped_matmul", "name": name,
                "rows": -(-M * K * held // E), "n": n, "k": k,
                "groups": held, "mult": Lm}

    ops = [
        mm("mla.q_down", M, rq, d, L), mm("mla.q_up", M, H * (dn + dr), rq, L),
        mm("mla.kv_down", M, r + dr, d, L), mm("mla.o", M, d, H * dv, L),
        mm("mlp.gate", M, f, d, Ld), mm("mlp.up", M, f, d, Ld),
        mm("mlp.down", M, d, f, Ld),
        mm("moe.router", M, E, d, Lm),
        mm("moe.shared_gate", M, fs, d, Lm), mm("moe.shared_up", M, fs, d, Lm),
        mm("moe.shared_down", M, d, fs, Lm),
        gmm("moe.experts.gate", fe, d), gmm("moe.experts.up", fe, d),
        gmm("moe.experts.down", d, fe),
        # prefill scores the last position only
        mm("lm_head", batch, V, d, 1),
    ]
    # the latent's expansion (prefill) and the absorbed products (decode)
    # run under the attention core's site scope, so they carry its name
    if phase == "prefill":
        ops += [mm("mla.core", M, H * dn, r, L),
                mm("mla.core", M, H * dv, r, L),
                {"kind": "mla_attention", "name": "mla.core", "phase": phase,
                 "batch": batch, "heads": H, "q_len": prompt_len,
                 "kv_len": prompt_len, "qk_dim": dn + dr, "v_dim": dv,
                 "latent": r, "rope": dr, "mult": L}]
    else:
        # absorbed: per head, the query's nope part into the latent and the
        # latent context back out, W_UK and W_UV read once
        ops += [mm("mla.core", batch, r, dn, H * L),
                mm("mla.core", batch, dv, r, H * L),
                {"kind": "mla_attention", "name": "mla.core", "phase": phase,
                 "batch": batch, "heads": H, "q_len": 1, "kv_len": pos + 1,
                 "qk_dim": dn + dr, "v_dim": dv, "latent": r, "rope": dr,
                 "mult": L}]
    return ops
