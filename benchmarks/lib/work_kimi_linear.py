"""The operations and bytes the step of a hybrid of Kimi Delta Attention
and latent attention without positions, behind a leading dense layer
and over expert layers, *requires* on the share of the model one chip
holds when its rows are packed documents, computed from the
configuration file's keys (Kimi-Linear's `config.json` spelling).

The same strict reckoning as `lib/work_hybrid_moe.py`: a backward pass
counts twice its forward, nothing recomputed counts, norms, activations,
the convolution's taps, the softmax, sorting and the optimizer are left
out, the delta rule counts as the recurrence it is, the held experts at
the share of the assignments uniform routing sends them.  Attention
counts **the query-key pairs the document mask and the causal mask
keep**: a document of L tokens keeps L (L + 1) / 2 of them, whatever a
kernel computes of the blocks the masks empty.  The pairs are counted
from the lengths of the documents the job's batches really hold
(`kept_pairs`), so the count moves with the traffic and with nothing
else.  A share computed from these can only be read too low, never
above 100%.
"""

from __future__ import annotations

from benchmarks.lib import work_hybrid_moe
from benchmarks.lib.work import adam_bytes  # noqa: F401  (one definition)


def sizes(config: dict) -> dict:
    """The configuration's keys under short names.  `layers` are the
    layers held here; `attends` those of them that attend, counted from
    0 (the source counts `full_attn_layers` from 1), the others are
    KDA; `dense` the leading layers with a dense SwiGLU, `expert_layers`
    the others; `held` the routed experts held of the `published` the
    router scores."""
    c = config
    linear = c["linear_attn_config"]
    layers = int(c["num_hidden_layers"])
    attends = tuple(i - 1 for i in linear["full_attn_layers"]
                    if i - 1 < layers)
    dense = min(int(c["first_k_dense_replace"]), layers)
    return {
        "hidden": int(c["hidden_size"]),
        "heads": int(c["num_attention_heads"]),
        "nope": int(c["qk_nope_head_dim"]),
        "rope": int(c["qk_rope_head_dim"]),
        "v": int(c["v_head_dim"]),
        "kv_rank": int(c["kv_lora_rank"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_dim": int(linear["head_dim"]),
        "taps": int(linear["short_conv_kernel_size"]),
        # the inner width of the decay's and the gate's pair
        "kda_rank": int(linear["head_dim"]),
        "ffn": int(c["intermediate_size"]),
        "expert_ffn": int(c["moe_intermediate_size"]),
        "held": int(c["num_experts"]),
        "published": int(c.get("num_experts_published", c["num_experts"])),
        "top_k": int(c["num_experts_per_token"]),
        "shared": int(c["num_shared_experts"]),
        "layers": layers,
        "attends": attends,
        "attention": len(attends),
        "kda": layers - len(attends),
        "dense": dense,
        "expert_layers": layers - dense,
        "vocab": int(c["vocab_size"]),
        "eod": int(c["eod_token_id"]),
        "positions": int(c["model_max_length"]),
    }


def _latent_matrices(s: dict) -> int:
    """Elements of W_q, W_kva, W_kvb and W_o."""
    h, nh = s["hidden"], s["heads"]
    return (h * nh * (s["nope"] + s["rope"]) + h * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * nh * (s["nope"] + s["v"]) + nh * s["v"] * h)


_kda_matrices = work_hybrid_moe._kda_matrices


def param_counts(s: dict) -> dict:
    """Parameters held here, by part (norm weights included)."""
    h = s["hidden"]
    wide = s["kda_heads"] * s["kda_dim"]
    # beside its matrices: three convolutions, A_h, dt_bias, the norm
    kda = (_kda_matrices(s) + 3 * s["taps"] * wide + s["kda_heads"] + wide
           + s["kda_dim"])
    latent = _latent_matrices(s) + s["kv_rank"]        # + the inner norm
    expert = 3 * h * s["expert_ffn"]
    expert_layer = (h * s["published"] + s["published"]
                    + s["held"] * expert + s["shared"] * expert)
    dense_mlp = 3 * h * s["ffn"]
    return {
        "latent": latent, "kda": kda, "norms_a_block": 2 * h,
        "dense_mlp": dense_mlp, "expert_layer": expert_layer,
        "held_experts_a_layer": s["held"] * expert,
        "embed_and_head": 2 * s["vocab"] * h,
        "total": (s["attention"] * latent + s["kda"] * kda
                  + s["layers"] * 2 * h + s["dense"] * dense_mlp
                  + s["expert_layers"] * expert_layer
                  + 2 * s["vocab"] * h + h),
    }


def document_lengths(tokens, eod: int) -> list:
    """The lengths of the documents of every row of `tokens` (rows of
    ids, nested lists or an array), the closing EOD included; a row's
    last document ends with the row."""
    lengths = []
    for row in tokens:
        start = 0
        row = list(row)
        for t, x in enumerate(row):
            if x == eod:
                lengths.append(t + 1 - start)
                start = t + 1
        if start < len(row):
            lengths.append(len(row) - start)
    return lengths


def kept_pairs(lengths) -> int:
    """The query-key pairs a causal mask within documents keeps."""
    return sum(n * (n + 1) // 2 for n in lengths)


def forward_flops_per_token(s: dict, pairs_per_token: float) -> dict:
    """Required forward FLOPs a token, by part.  `pairs_per_token`: the
    keys a token meets, the mean over the job's batches (`kept_pairs` /
    tokens); QK^T runs over nope + rope and P.V over v."""
    h = s["hidden"]
    expert = 2 * 3 * h * s["expert_ffn"]
    return {
        "latent_projections": s["attention"] * 2 * _latent_matrices(s),
        "attention": s["attention"] * s["heads"] * pairs_per_token
        * 2 * (s["nope"] + s["rope"] + s["v"]),
        "kda_projections": s["kda"] * 2 * _kda_matrices(s),
        "scan": s["kda"] * s["kda_heads"]
        * work_hybrid_moe.scan_flops_per_token_head(s),
        "dense_mlp": s["dense"] * 2 * 3 * h * s["ffn"],
        "router": s["expert_layers"] * 2 * h * s["published"],
        "shared_expert": s["expert_layers"] * s["shared"] * expert,
        "held_experts": s["expert_layers"] * expert
        * s["top_k"] * s["held"] / s["published"],
        "head": 2 * h * s["vocab"],
    }


def train_flops_per_token(s: dict, pairs_per_token: float) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * sum(forward_flops_per_token(s, pairs_per_token).values())


def flash_attention_work(s: dict, pairs: float, tokens: int,
                         bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of every latent-attention
    layer held, forward and backward, at `pairs` kept query-key pairs
    over `tokens` tokens a step.

    FLOPs: a pair and head, QK^T at 2 (nope + rope) and P.V at 2 v; the
    backward's four matmuls are twice that.  Bytes, as
    `lib/work_mla_moe.py` counts them: q and k forward, q, k, dq, dk
    backward at nope + rope a head; v and o forward, v, o, do, dv
    backward at v."""
    d_qk, d_v = s["nope"] + s["rope"], s["v"]
    forward = 2 * (d_qk + d_v) * pairs * s["heads"]
    return {"flops": s["attention"] * 3 * forward,
            "bytes": s["attention"] * 6 * (d_qk + d_v) * s["heads"] * tokens
            * bytes_per_element}


def scan_work(s: dict, batch: int, seq: int) -> dict:
    """`lib/work_hybrid_moe.py::scan_work` at this configuration's
    heads and KDA layers: a boundary takes nothing off the recurrence
    (a token's update is the same seven d_k d_v whatever state it
    finds) and adds a mask of a bit a token, which is not counted."""
    return work_hybrid_moe.scan_work(s, batch, seq)


def expert_gemm_work(s: dict, tokens: int) -> dict:
    """`lib/work_hybrid_moe.py::expert_gemm_work` over the expert
    layers held (the leading dense layer has no experts)."""
    return work_hybrid_moe.expert_gemm_work(
        dict(s, layers=s["expert_layers"]), tokens)
