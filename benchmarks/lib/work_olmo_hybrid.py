"""The operations and bytes the step of a dense hybrid of Gated DeltaNet
and full attention (Olmo-Hybrid) *requires* on the share of the model
one chip holds when its rows are packed documents, computed from the
configuration file's keys (Olmo-Hybrid's `config.json` spelling).

The strict reckoning of `lib/work_kimi_linear.py`: a backward pass
counts twice its forward, nothing recomputed counts, norms,
activations, the convolution's taps, the softmax and the optimizer are
left out, the delta rule counts as the recurrence it is, attention at
the query-key pairs the document and the causal mask keep (`kept_pairs`
of the documents the job's batches hold).  A share computed from these
can only be read too low, never above 100%.
"""

from __future__ import annotations

from benchmarks.lib.work import adam_bytes  # noqa: F401  (one definition)
from benchmarks.lib.work_kimi_linear import (  # noqa: F401  (one each)
    document_lengths,
    kept_pairs,
)


def sizes(config: dict) -> dict:
    """The configuration's keys under short names.  `layers` are the
    layers held here, `attends` those of them whose `layer_types` entry
    is full attention (counted from 0), the others Gated DeltaNet."""
    c = config
    layers = int(c["num_hidden_layers"])
    attends = tuple(i for i, kind in enumerate(c["layer_types"][:layers])
                    if kind == "full_attention")
    heads = int(c["num_attention_heads"])
    return {
        "hidden": int(c["hidden_size"]),
        "heads": heads,
        "head_dim": int(c["hidden_size"]) // heads,
        "gdn_heads": int(c["linear_num_key_heads"]),
        "key_dim": int(c["linear_key_head_dim"]),
        "value_dim": int(c["linear_value_head_dim"]),
        "taps": int(c["linear_conv_kernel_dim"]),
        "neg_eigval": bool(c["linear_allow_neg_eigval"]),
        "ffn": int(c["intermediate_size"]),
        "layers": layers,
        "attends": attends,
        "attention": len(attends),
        "gdn": layers - len(attends),
        "vocab": int(c["vocab_size"]),
        "eod": int(c["eod_token_id"]),
        "positions": int(c["max_position_embeddings"]),
    }


def _gdn_matrices(s: dict) -> int:
    """Elements of W_q, W_k, W_v, W_g, W_o, W_a and W_b."""
    h, n = s["hidden"], s["gdn_heads"]
    return 2 * h * n * s["key_dim"] + 3 * h * n * s["value_dim"] + 2 * h * n


def _attention_matrices(s: dict) -> int:
    """Elements of W_q, W_k, W_v and W_o."""
    return 4 * s["hidden"] * s["heads"] * s["head_dim"]


def param_counts(s: dict) -> dict:
    """Parameters held here, by part (norm weights included)."""
    h, n = s["hidden"], s["gdn_heads"]
    # beside its matrices: three convolutions, A_log, dt_bias, the norm
    gdn = (_gdn_matrices(s) + s["taps"] * n * (2 * s["key_dim"]
                                               + s["value_dim"])
           + 2 * n + s["value_dim"])
    attention = _attention_matrices(s) + 2 * s["heads"] * s["head_dim"]
    dense_mlp = 3 * h * s["ffn"]
    return {
        "gdn": gdn, "attention": attention, "norms_a_block": 2 * h,
        "dense_mlp": dense_mlp, "embed_and_head": 2 * s["vocab"] * h,
        "total": (s["gdn"] * gdn + s["attention"] * attention
                  + s["layers"] * (dense_mlp + 2 * h)
                  + 2 * s["vocab"] * h + h),
    }


def scan_flops_per_token_head(s: dict) -> int:
    """Forward FLOPs a token and head of the recurrence: the decay of
    the state (d_k d_v multiplies), what the state holds for k (2 d_k
    d_v), the rank-one update (2 d_k d_v) and the read by q (2 d_k d_v)
    = 7 d_k d_v."""
    return 7 * s["key_dim"] * s["value_dim"]


def forward_flops_per_token(s: dict, pairs_per_token: float) -> dict:
    """Required forward FLOPs a token, by part.  `pairs_per_token`: the
    keys a token meets, the mean over the job's batches (`kept_pairs` /
    tokens); QK^T and P.V each run over a head's width."""
    h = s["hidden"]
    return {
        "gdn_projections": s["gdn"] * 2 * _gdn_matrices(s),
        "scan": s["gdn"] * s["gdn_heads"] * scan_flops_per_token_head(s),
        "attention_projections": s["attention"] * 2 * _attention_matrices(s),
        "attention": s["attention"] * s["heads"] * pairs_per_token
        * 2 * 2 * s["head_dim"],
        "dense_mlp": s["layers"] * 2 * 3 * h * s["ffn"],
        "head": 2 * h * s["vocab"],
    }


def train_flops_per_token(s: dict, pairs_per_token: float) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * sum(forward_flops_per_token(s, pairs_per_token).values())


def flash_attention_work(s: dict, pairs: float, tokens: int,
                         bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes a step of every full-attention
    layer held, forward and backward, at `pairs` kept query-key pairs
    over `tokens` tokens a step, as `lib/work_kimi_linear.py` counts
    them with keys and values of one width."""
    d = s["head_dim"]
    forward = 2 * 2 * d * pairs * s["heads"]
    return {"flops": s["attention"] * 3 * forward,
            "bytes": s["attention"] * 6 * 2 * d * s["heads"] * tokens
            * bytes_per_element}


def scan_work(s: dict, batch: int, seq: int, bytes_per_element: int = 2,
              decay_bytes: int = 4) -> dict:
    """Required FLOPs and HBM bytes a step of the delta rule of every
    Gated DeltaNet layer held, forward and backward, whatever
    implements it.

    FLOPs: `scan_flops_per_token_head` forward, twice that backward.
    Bytes: forward q, k, v, the log-decay g and beta (float32, a scalar
    each a head and token) read and o written, once; backward those and
    do read and the five gradients written, once.  A boundary takes
    nothing off the recurrence."""
    n, dk, dv = s["gdn_heads"], s["key_dim"], s["value_dim"]
    tokens = batch * seq
    rows = (2 * dk + dv) * bytes_per_element + 2 * decay_bytes
    o = dv * bytes_per_element
    forward = rows + o
    backward = (rows + o) + rows     # read again, with do; five gradients
    return {"flops": s["gdn"] * 3 * tokens * n
            * scan_flops_per_token_head(s),
            "bytes": s["gdn"] * tokens * n * (forward + backward)}
