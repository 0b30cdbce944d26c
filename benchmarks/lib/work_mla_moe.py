"""The operations and bytes the DeepSeek-V3 family's step *requires*
on the share of the model one chip holds, computed from the
configuration file's keys.

The same strict reckoning as `lib/work.py`: causal attention counts the
unmasked query-key pairs, a backward pass counts twice its forward,
nothing recomputed counts, and norms, activations, the rotary
embedding, the softmax, sorting and the optimizer are left out (each
bandwidth-bound and under 1%).  The held experts count at the share of
the assignments uniform routing sends them, `top_k * held / published`
experts a token: what the router really sent is a counter of the run
(`moe_load_imbalance`), not a property of the shapes.  So a share
computed from these can only be read too low, never above 100%.
"""

from __future__ import annotations

from benchmarks.lib.work import adam_bytes  # noqa: F401  (one definition)


def sizes(config: dict) -> dict:
    """The configuration's keys under short names.  `layers` are the
    main model's layers held here, `dense` the leading dense ones among
    them, `mtp` the multi-token-prediction modules (each one more
    expert block), `held` the routed experts held of the `published`
    the router scores."""
    c = config
    return {
        "hidden": int(c["hidden_size"]),
        "heads": int(c["num_attention_heads"]),
        "q_rank": int(c["q_lora_rank"]),
        "kv_rank": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]),
        "v": int(c["v_head_dim"]),
        "ffn": int(c["intermediate_size"]),
        "expert_ffn": int(c["moe_intermediate_size"]),
        "held": int(c["n_routed_experts"]),
        "published": int(c.get("n_routed_experts_published",
                               c["n_routed_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "shared": int(c["n_shared_experts"]),
        "layers": int(c["num_hidden_layers"]),
        "dense": int(c["first_k_dense_replace"]),
        "mtp": int(c.get("num_nextn_predict_layers", 0)),
        "vocab": int(c["vocab_size"]),
        "positions": int(c["max_position_embeddings"]),
    }


def expert_blocks(s: dict) -> int:
    """Blocks with an expert layer: the main model's, and one a
    multi-token-prediction module."""
    return s["layers"] - s["dense"] + s["mtp"]


def _mla_matrices(s: dict) -> int:
    """Elements of the latent attention's five projections: W_qa, W_qb,
    W_kva, W_kvb, W_o."""
    h, nh = s["hidden"], s["heads"]
    return (h * s["q_rank"] + s["q_rank"] * nh * (s["nope"] + s["rope"])
            + h * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * nh * (s["nope"] + s["v"]) + nh * s["v"] * h)


def param_counts(s: dict) -> dict:
    """Parameters held here, by part (norm weights included)."""
    h = s["hidden"]
    mla = _mla_matrices(s) + s["q_rank"] + s["kv_rank"]   # + two inner norms
    expert = 3 * h * s["expert_ffn"]
    expert_layer = (h * s["published"] + s["published"]
                    + s["held"] * expert + s["shared"] * expert)
    blocks = s["layers"] + s["mtp"]
    return {
        "mla": mla, "norms_a_block": 2 * h,
        "dense_mlp": 3 * h * s["ffn"],
        "expert_layer": expert_layer,
        "held_experts_a_layer": s["held"] * expert,
        "mtp_module": s["mtp"] * (2 * h * h + 3 * h),
        "embed_and_head": 2 * s["vocab"] * h,
        "total": (blocks * (mla + 2 * h) + s["dense"] * 3 * h * s["ffn"]
                  + expert_blocks(s) * expert_layer
                  + s["mtp"] * (2 * h * h + 3 * h)
                  + 2 * s["vocab"] * h + h),
    }


def forward_flops_per_token(s: dict, seq: int) -> dict:
    """Required forward FLOPs a token, by part.  A token at position i
    meets i keys, (seq + 1) / 2 on average; QK^T runs over nope + rope
    and P.V over v."""
    h, nh = s["hidden"], s["heads"]
    blocks = s["layers"] + s["mtp"]
    mla = 2 * _mla_matrices(s)
    attention = nh * (seq + 1) * (s["nope"] + s["rope"] + s["v"])
    expert = 2 * 3 * h * s["expert_ffn"]
    return {
        "mla_projections": blocks * mla,
        "attention": blocks * attention,
        "dense_mlp": s["dense"] * 2 * 3 * h * s["ffn"],
        "router": expert_blocks(s) * 2 * h * s["published"],
        "shared_expert": expert_blocks(s) * s["shared"] * expert,
        "held_experts": expert_blocks(s) * expert
        * s["top_k"] * s["held"] / s["published"],
        "heads": (1 + s["mtp"]) * 2 * h * s["vocab"],
        "mtp_projection": s["mtp"] * 2 * 2 * h * h,
    }


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * sum(forward_flops_per_token(s, seq).values())


def flash_attention_work(batch: int, heads: int, seq: int, d_qk: int,
                         d_v: int, bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes of one causal attention layer whose
    keys are `d_qk` wide and whose values `d_v`, forward and backward.

    FLOPs: over the S(S+1)/2 unmasked pairs, QK^T at 2 d_qk and P.V at
    2 d_v; the backward's four matmuls (dV and dP at d_v, dQ and dK at
    d_qk) are twice that.  Bytes: q and k forward, q, k, dq, dk
    backward at d_qk; v and o forward, v, o, do, dv backward at d_v.
    At d_qk == d_v this is `lib/work.py::flash_attention_work`."""
    pairs = seq * (seq + 1) // 2
    forward = 2 * (d_qk + d_v) * pairs * batch * heads
    return {"flops": 3 * forward,
            "bytes": 6 * (d_qk + d_v) * batch * heads * seq
            * bytes_per_element}


def expert_gemm_work(s: dict, tokens: int, bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of the grouped GEMMs of
    every expert layer held, at the assignments uniform routing sends
    the held experts (`rows` a layer).

    FLOPs: three (H, f) products a row forward, twice that backward.
    Bytes: each held expert's three matrices are read forward, read for
    the input gradient and written as their own gradient; a row's
    activations are, forward, its input read, gate and up written,
    their product read and the output written, and backward the same
    tensors' gradients the other way round plus the saved input, gate
    and up and product read again for the weight gradients."""
    h, f = s["hidden"], s["expert_ffn"]
    rows = tokens * s["top_k"] * s["held"] / s["published"]
    layers = expert_blocks(s)
    weights = s["held"] * 3 * h * f
    forward_row = h + 2 * f + f + h
    backward_row = 2 * forward_row + (h + f)
    return {
        "rows": rows,
        "flops": layers * 3 * rows * 2 * 3 * h * f,
        "bytes": layers * bytes_per_element * (
            3 * weights + rows * (forward_row + backward_row)),
    }
