"""Model FLOPs of one HSTU training sample's forward pass, from shapes.

Matrix products only (2 FLOPs per multiply-add); norms, activations and the
softmax are left out. Causal attention counts the position pairs it needs,
``S (S + 1) / 2``. Recomputation for the backward pass is not counted: the
training sample is 3 forward passes (``bench.spec.flops_per_sample``).
"""


def forward_flops_per_sample(cfg) -> float:
    s, d, nl = cfg["seq_len"], cfg["d_model"], cfg["n_layers"]
    demb = max(t["dim"] for t in cfg["tables"])
    pairs = s * (s + 1) / 2
    in_proj = 2 * s * demb * d
    layer = (2 * s * d * 4 * d  # U, V, Q, K projection
             + 2 * 2 * pairs * d  # Q K^T and A V over the heads
             + 2 * s * d * d)  # output projection
    targets = 2 * (s - 1) * demb * d  # next-item targets through in_proj
    infonce = 2 * (s - 1) * (s - 1) * d  # in-sequence logits
    return float(in_proj + nl * layer + targets + infonce)
