"""Model FLOPs of one toy decoder training sample's forward pass, from
shapes: matrix products only (2 FLOPs per multiply-add), causal attention
over the ``S (S + 1) / 2`` position pairs it needs, the head over the
first table's rows as the configuration file gives them (one chip's)."""


def forward_flops_per_sample(cfg) -> float:
    s, d, f = cfg["seq_len"], cfg["d_model"], cfg["d_ff"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    pairs = s * (s + 1) / 2
    layer = (2 * s * d * (2 * q + 2 * kv)  # Q, K, V and output projections
             + 2 * 2 * pairs * q  # Q K^T and A V over the heads
             + 2 * s * d * f * 3)  # SwiGLU
    head = 2 * s * d * cfg["tables"][0]["vocab_size"]
    return float(cfg["n_layers"] * layer + head)
