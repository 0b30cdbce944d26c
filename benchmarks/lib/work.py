"""The operations and bytes the work *requires*, computed from shapes.

Everything a roofline share or an MFU divides by lives here, with the
benchmark, so that no PR that claims a gain can change what the gain is
measured against.  "Required" means what the mathematics needs and no
more: causal attention counts the S(S+1)/2 query-key pairs that are not
masked (half the square, not the whole of it), a backward pass counts
twice its forward (dgrad and wgrad), and nothing recomputed counts —
neither activation checkpointing nor the flash kernel's own recompute of
the scores in its backward.  So a share computed from these can only be
read too low, never above 100%.
"""

from __future__ import annotations

# the configuration files keep their source's key names; these are the
# spellings of GPT-2's and OPT's config.json
_SIZE_KEYS = {
    "hidden": ("n_embd", "hidden_size"),
    "layers": ("n_layer", "num_hidden_layers"),
    "heads": ("n_head", "num_attention_heads"),
    "ffn": ("n_inner", "ffn_dim", "intermediate_size"),
    "vocab": ("vocab_size",),
    "positions": ("n_positions", "max_position_embeddings"),
}


def model_sizes(config: dict) -> dict:
    """hidden, layers, heads, ffn, vocab, positions of a configuration
    file, whichever of the source's spellings it uses.  GPT-2 leaves
    `n_inner` null for 4 x hidden."""
    out = {}
    for name, keys in _SIZE_KEYS.items():
        values = [config[k] for k in keys if config.get(k) is not None]
        if not values:
            if name == "ffn":
                continue
            raise KeyError(f"configuration has none of {keys}")
        out[name] = int(values[0])
    out.setdefault("ffn", 4 * out["hidden"])
    return out


def gpt_param_count(sizes: dict, seq: int) -> int:
    """Parameters of the GPT-2 network as the repo builds it: tied
    embedding, a learned position table of `seq` rows, pre-LN blocks
    with biases everywhere, a final LayerNorm."""
    h, f = sizes["hidden"], sizes["ffn"]
    block = (2 * h) + (h * 3 * h + 3 * h) + (h * h + h) + (2 * h) \
        + (h * f + f) + (f * h + h)
    return sizes["vocab"] * h + seq * h + sizes["layers"] * block + 2 * h


def gpt_train_flops_per_token(sizes: dict, seq: int) -> int:
    """Required forward + backward FLOPs per trained token.

    Forward, per token: each block's four GEMMs, 2 x (3h^2 + h^2 + 2hf);
    causal attention, where a token at position i meets i keys, so
    (seq + 1) / 2 on average, in two matmuls of 2 x h each:
    2 h (seq + 1); the tied head, 2 h V over the vocabulary as run (the
    padded one).  The backward is twice the forward.  Norms, GELU,
    softmax and the optimizer are left out (under 1%, bandwidth-bound).
    """
    h, f = sizes["hidden"], sizes["ffn"]
    block = 2 * (4 * h * h + 2 * h * f) + 2 * h * (seq + 1)
    forward = sizes["layers"] * block + 2 * h * sizes["vocab"]
    return 3 * forward


def flash_attention_work(batch: int, heads: int, seq: int, head_dim: int,
                         bytes_per_element: int = 2) -> dict:
    """Required FLOPs and HBM bytes of one causal attention layer,
    forward and backward together, for `batch` sequences on one device.

    FLOPs: the forward is two matmuls over the S(S+1)/2 unmasked pairs,
    2 x 2 x d each pair; the backward needs four (dV, dP, dQ, dK), twice
    the forward.  The kernel's recompute of QK^T in the backward is not
    required work.  Bytes: the forward reads q, k, v and writes o; the
    backward reads q, k, v, o, do and writes dq, dk, dv — twelve
    (B, H, S, d) arrays; the per-row softmax statistics are left out.
    """
    pairs = seq * (seq + 1) // 2
    forward = 2 * 2 * head_dim * pairs * batch * heads
    return {"flops": 3 * forward,
            "bytes": 12 * batch * heads * seq * head_dim * bytes_per_element}


def adam_bytes(n_elements: int, state_bytes: int, grad_bytes: int) -> int:
    """HBM bytes one Adam update of a flat buffer must move: read the
    parameter, both moments and the gradient, write the parameter and
    both moments."""
    return n_elements * (6 * state_bytes + grad_bytes)
