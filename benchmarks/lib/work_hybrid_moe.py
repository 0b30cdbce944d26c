"""The operations and bytes the step of a hybrid of gated grouped-query
attention and Kimi Delta Attention over expert layers *requires* on
the share of the model one chip holds, computed from the configuration
file's keys (Solar-Open2's `config.json` spelling).

The same strict reckoning as `lib/work.py` and `lib/work_mla_moe.py`:
causal attention counts the unmasked query-key pairs, a backward pass
counts twice its forward, nothing recomputed counts, and norms,
activations, the convolution's four taps, the softmax, sorting and the
optimizer are left out (each bandwidth-bound and under 1%).  The delta
rule counts as the recurrence it is, a token at a time, whatever
implements it: a chunked form does other work (more of it on the MXU,
less of it in order), and none of that is required.  The held experts
count at the share of the assignments uniform routing sends them.  So
a share computed from these can only be read too low, never above
100%.
"""

from __future__ import annotations

from benchmarks.lib.work import adam_bytes  # noqa: F401  (one definition)


def sizes(config: dict) -> dict:
    """The configuration's keys under short names.  `layers` are the
    layers held here, `attention` those of them that attend (the
    others are KDA), `held` the routed experts held of the `published`
    the router scores."""
    c = config
    linear = c["linear_attn_config"]
    layers = int(c["num_hidden_layers"])
    attends = tuple(i for i in c["gqa_layers"] if i < layers)
    return {
        "hidden": int(c["hidden_size"]),
        "heads": int(c["num_attention_heads"]),
        "kv_heads": int(c["num_key_value_heads"]),
        "head_dim": int(c["head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_dim": int(linear["head_dim"]),
        "taps": int(linear["short_conv_kernel_size"]),
        # the inner width of the decay's and the gate's pair
        "kda_rank": int(linear["head_dim"]),
        "expert_ffn": int(c["moe_intermediate_size"]),
        "held": int(c["n_routed_experts"]),
        "published": int(c.get("n_routed_experts_published",
                               c["n_routed_experts"])),
        "top_k": int(c["num_experts_per_tok"]),
        "shared": int(c["n_shared_experts"]),
        "layers": layers,
        "attends": attends,
        "attention": len(attends),
        "kda": layers - len(attends),
        "vocab": int(c["vocab_size"]),
        "positions": int(c["max_position_embeddings"]),
    }


def _attention_matrices(s: dict) -> int:
    """Elements of W_q, W_gate, W_o and W_k, W_v."""
    h, wide = s["hidden"], s["heads"] * s["head_dim"]
    return 3 * h * wide + 2 * h * s["kv_heads"] * s["head_dim"]


def _kda_matrices(s: dict) -> int:
    """Elements of W_q, W_k, W_v, W_o, the two rank pairs and W_beta."""
    h, wide, r = s["hidden"], s["kda_heads"] * s["kda_dim"], s["kda_rank"]
    return 4 * h * wide + 2 * (h * r + r * wide) + h * s["kda_heads"]


def param_counts(s: dict) -> dict:
    """Parameters held here, by part (norm weights included)."""
    h = s["hidden"]
    wide = s["kda_heads"] * s["kda_dim"]
    # beside its matrices: three convolutions, A_h, dt_bias, the norm
    kda = (_kda_matrices(s) + 3 * s["taps"] * wide + s["kda_heads"] + wide
           + s["kda_dim"])
    expert = 3 * h * s["expert_ffn"]
    expert_layer = (h * s["published"] + s["published"]
                    + s["held"] * expert + s["shared"] * expert)
    return {
        "attention": _attention_matrices(s), "kda": kda,
        "norms_a_block": 2 * h, "expert_layer": expert_layer,
        "held_experts_a_layer": s["held"] * expert,
        "embed_and_head": 2 * s["vocab"] * h,
        "total": (s["attention"] * _attention_matrices(s) + s["kda"] * kda
                  + s["layers"] * (2 * h + expert_layer)
                  + 2 * s["vocab"] * h + h),
    }


def scan_flops_per_token_head(s: dict) -> int:
    """Forward FLOPs a token and head of the recurrence: the decay of
    the state (d_k d_v multiplies), what the state holds for k (2 d_k
    d_v), the rank-one update (2 d_k d_v) and the read by q (2 d_k d_v)
    = 7 d_k d_v."""
    return 7 * s["kda_dim"] * s["kda_dim"]


def forward_flops_per_token(s: dict, seq: int) -> dict:
    """Required forward FLOPs a token, by part.  A token at position i
    meets i keys, (seq + 1) / 2 on average."""
    h = s["hidden"]
    expert = 2 * 3 * h * s["expert_ffn"]
    return {
        "attention_projections": s["attention"] * 2 * _attention_matrices(s),
        "attention": s["attention"] * s["heads"] * (seq + 1)
        * 2 * s["head_dim"],
        "kda_projections": s["kda"] * 2 * _kda_matrices(s),
        "scan": s["kda"] * s["kda_heads"] * scan_flops_per_token_head(s),
        "router": s["layers"] * 2 * h * s["published"],
        "shared_expert": s["layers"] * s["shared"] * expert,
        "held_experts": s["layers"] * expert
        * s["top_k"] * s["held"] / s["published"],
        "head": 2 * h * s["vocab"],
    }


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * sum(forward_flops_per_token(s, seq).values())


def flash_attention_work(batch: int, heads: int, kv_heads: int, seq: int,
                         head_dim: int, bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes of one causal grouped-query
    attention layer, forward and backward.

    FLOPs: every query head's S(S+1)/2 unmasked pairs at 2 x 2 d
    forward, twice that backward.  Bytes: q, o forward and q, o, do, dq
    backward for every query head; k, v forward and k, v, dk, dv
    backward for every kv head, once: a kv head is read once however
    many query heads it serves."""
    pairs = seq * (seq + 1) // 2
    forward = 2 * 2 * head_dim * pairs * batch * heads
    return {"flops": 3 * forward,
            "bytes": 6 * (heads + kv_heads) * batch * seq * head_dim
            * bytes_per_element}


def scan_work(s: dict, batch: int, seq: int, bytes_per_element: int = 2,
              decay_bytes: int = 4) -> dict:
    """Required FLOPs and HBM bytes a step of the delta rule of every
    KDA layer held, forward and backward, whatever implements it.

    FLOPs: `scan_flops_per_token_head` forward, twice that backward.
    Bytes: forward q, k, v, the log-decay g (float32) and beta (float32,
    a scalar a head) read and o written, once; backward those and do
    read and the five gradients written, once."""
    n, d = s["kda_heads"], s["kda_dim"]
    tokens = batch * seq
    rows = 3 * d * bytes_per_element + d * decay_bytes + decay_bytes
    o = d * bytes_per_element
    forward = rows + o
    backward = (rows + o) + rows     # read again, with do; five gradients
    return {"flops": s["kda"] * 3 * tokens * n
            * scan_flops_per_token_head(s),
            "bytes": s["kda"] * tokens * n * (forward + backward)}


def expert_gemm_work(s: dict, tokens: int, bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of the grouped GEMMs of
    every expert layer held, at the assignments uniform routing sends
    the held experts: `lib/work_mla_moe.py::expert_gemm_work` at this
    configuration's counts."""
    h, f = s["hidden"], s["expert_ffn"]
    rows = tokens * s["top_k"] * s["held"] / s["published"]
    weights = s["held"] * 3 * h * f
    forward_row = h + 2 * f + f + h
    backward_row = 2 * forward_row + (h + f)
    return {
        "rows": rows,
        "flops": s["layers"] * 3 * rows * 2 * 3 * h * f,
        "bytes": s["layers"] * bytes_per_element * (
            3 * weights + rows * (forward_row + backward_row)),
    }
