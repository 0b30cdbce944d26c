"""The operations and bytes the step of a decoder of gated short
convolutions and QK-normed rotary grouped-query attention over expert
layers without a shared expert *requires* on the share of the model one
chip holds, computed from the configuration file's keys (LFM2's
`lfm2_moe` `config.json` spelling).

The same strict reckoning as `lib/work.py` and `lib/work_mla_moe.py`:
causal attention counts the unmasked query-key pairs, a backward pass
counts twice its forward, nothing recomputed counts, and norms, gates,
the convolution's taps, the rotary embedding, the softmax, sorting and
the optimizer are left out of the FLOPs (each bandwidth-bound and under
1%).  The held experts count at the share of the assignments uniform
routing sends them, `top_k * held / published` experts a token: what
the router really sent is a counter of the run (`top4_load_imbalance`).
So a share computed from these can only be read too low, never above
100%.
"""

from __future__ import annotations

from benchmarks.lib.work import adam_bytes  # noqa: F401  (one definition)

# the source's names for a layer's mixer -> the program's
KINDS = {"conv": "conv", "full_attention": "attention"}


def sizes(config: dict) -> dict:
    """The configuration's keys under short names.  `layers` are the
    layers held here and `kinds` their mixers under the program's names;
    `conv` and `attention` count them; `dense` the leading layers with
    a dense SwiGLU, `expert_layers` the others; `held` the routed
    experts held of the `published` the router scores."""
    c = config
    layers = int(c["num_hidden_layers"])
    kinds = tuple(KINDS[k] for k in c["layer_types"][:layers])
    if len(kinds) != layers:
        raise ValueError(f"layer_types names {len(kinds)} of the "
                         f"{layers} layers held")
    dense = min(int(c["num_dense_layers"]), layers)
    heads = int(c["num_attention_heads"])
    return {
        "hidden": int(c["hidden_size"]),
        "heads": heads,
        "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(c["hidden_size"]) // heads,
        "taps": int(c["conv_L_cache"]),
        "ffn": int(c["intermediate_size"]),
        "expert_ffn": int(c["moe_intermediate_size"]),
        "held": int(c["num_experts"]),
        "published": int(c.get("num_experts_published", c["num_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "layers": layers,
        "kinds": kinds,
        "conv": kinds.count("conv"),
        "attention": kinds.count("attention"),
        "dense": dense,
        "expert_layers": layers - dense,
        "tied": bool(c["tie_word_embeddings"]),
        "vocab": int(c["vocab_size"]),
        "positions": int(c["max_position_embeddings"]),
    }


def _conv_matrices(s: dict) -> int:
    """Elements of W_in (H, 3H) and W_out (H, H)."""
    return 4 * s["hidden"] * s["hidden"]


def _attention_matrices(s: dict) -> int:
    """Elements of W_q, W_k, W_v and W_o."""
    h, d = s["hidden"], s["head_dim"]
    return 2 * h * s["heads"] * d + 2 * h * s["kv_heads"] * d


def param_counts(s: dict) -> dict:
    """Parameters held here, by part (norm weights included)."""
    h = s["hidden"]
    conv = _conv_matrices(s) + s["taps"] * h
    attention = _attention_matrices(s) + 2 * s["head_dim"]
    expert = 3 * h * s["expert_ffn"]
    expert_layer = (h * s["published"] + s["published"]
                    + s["held"] * expert)
    dense_mlp = 3 * h * s["ffn"]
    ends = (1 if s["tied"] else 2) * s["vocab"] * h
    return {
        "conv": conv, "attention": attention, "norms_a_block": 2 * h,
        "dense_mlp": dense_mlp, "expert_layer": expert_layer,
        "held_experts_a_layer": s["held"] * expert,
        "embed_and_head": ends,
        "total": (s["conv"] * conv + s["attention"] * attention
                  + s["layers"] * 2 * h + s["dense"] * dense_mlp
                  + s["expert_layers"] * expert_layer + ends + h),
    }


def forward_flops_per_token(s: dict, seq: int) -> dict:
    """Required forward FLOPs a token, by part.  A token at position i
    meets i keys, (seq + 1) / 2 on average, in two matmuls of 2 d a
    query head each.  The head is a GEMM whether or not its weight is
    the embedding's."""
    h = s["hidden"]
    expert = 2 * 3 * h * s["expert_ffn"]
    return {
        "conv_projections": s["conv"] * 2 * _conv_matrices(s),
        "attention_projections": s["attention"] * 2 * _attention_matrices(s),
        "attention": s["attention"] * s["heads"] * (seq + 1)
        * 2 * s["head_dim"],
        "dense_mlp": s["dense"] * 2 * 3 * h * s["ffn"],
        "router": s["expert_layers"] * 2 * h * s["published"],
        "held_experts": s["expert_layers"] * expert
        * s["top_k"] * s["held"] / s["published"],
        "head": 2 * h * s["vocab"],
    }


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * sum(forward_flops_per_token(s, seq).values())


def flash_attention_work(s: dict, batch: int, seq: int,
                         bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of every attention layer
    held, forward and backward: the query heads' FLOPs over the
    S (S + 1) / 2 unmasked pairs (two matmuls forward, four backward),
    and the bytes of what has to move: q, o forward and q, o, do, dq
    backward at the query heads; k, v forward and k, v, dk, dv backward
    at the kv heads, each read once for its group."""
    d = s["head_dim"]
    pairs = seq * (seq + 1) // 2
    forward = 2 * 2 * d * pairs * batch * s["heads"]
    return {"flops": s["attention"] * 3 * forward,
            "bytes": s["attention"] * 6 * (s["heads"] + s["kv_heads"])
            * batch * seq * d * bytes_per_element}


def short_conv_work(s: dict, tokens: int, bytes_per_element: int = 2) -> dict:
    """Required HBM bytes a step of the gates and taps of every
    convolutional mixer held (`ops.short_conv.gated_short_conv`): the
    forward reads the projection's 3H and writes H a token; the
    backward reads them again with the output's gradient (4H) and
    writes the projection's gradient (3H).  The taps (H x taps, and
    their gradient) are under a thousandth of that.  Its FLOPs are a
    dozen a channel and token: bandwidth is the bound."""
    per_token = (3 + 1) + (4 + 3)
    return {"bytes": s["conv"] * per_token * s["hidden"] * tokens
            * bytes_per_element}


def expert_gemm_work(s: dict, tokens: int, bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of the grouped GEMMs of
    every expert layer held, at the assignments uniform routing sends
    the held experts (`rows` a layer), as `lib/work_mla_moe.py` counts
    them: three (H, f) products a row forward, twice that backward;
    each held expert's three matrices read forward, read for the input
    gradient and written as their own gradient; a row's activations
    forward (input read, gate and up written, their product read, the
    output written) and backward (the same tensors' gradients the other
    way round, plus the saved input, gate and up and product read again
    for the weight gradients)."""
    h, f = s["hidden"], s["expert_ffn"]
    rows = tokens * s["top_k"] * s["held"] / s["published"]
    layers = s["expert_layers"]
    weights = s["held"] * 3 * h * f
    forward_row = h + 2 * f + f + h
    backward_row = 2 * forward_row + (h + f)
    return {
        "rows": rows,
        "flops": layers * 3 * rows * 2 * 3 * h * f,
        "bytes": layers * bytes_per_element * (
            3 * weights + rows * (forward_row + backward_row)),
    }
