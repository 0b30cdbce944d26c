"""Device milliseconds a step owned by the latent attention's five
projections and its rotary embedding (`block*/attn/q_a`, `q_b`, `kv_a`,
`kv_b`, `rope`, `proj`; the two inner norms count under `q_a` and
`kv_a`), forward and backward, all layers, first device."""

from benchmarks.lib import owners


def compute(observed):
    return owners.ms(observed,
                     owner=r"block\d*/attn/(q_a|q_b|kv_a|kv_b|rope|proj)$")
