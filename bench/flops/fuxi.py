"""Model FLOPs of one FuXi training sample's forward pass, from shapes.

Matrix products only (2 FLOPs per multiply-add); norms, rotary positions,
activations and the softmax are left out. Causal attention counts the
position pairs it needs, ``S (S + 1) / 2``. Recomputation for the backward
pass is not counted: the training sample is 3 forward passes
(``bench.spec.flops_per_sample``).
"""


def forward_flops_per_sample(cfg) -> float:
    s, d, f, nl = cfg["seq_len"], cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    demb = max(t["dim"] for t in cfg["tables"])
    pairs = s * (s + 1) / 2
    in_proj = 2 * s * demb * d
    layer = (4 * 2 * s * d * d  # Q, K, V and output projections
             + 2 * 2 * pairs * d  # Q K^T and softmax(.) V over the heads
             + 2 * s * d * f  # up projection
             + cfg["fi_orders"] * 2 * s * f * f  # interaction orders
             + 2 * s * f * d)  # down projection
    targets = 2 * (s - 1) * demb * d
    infonce = 2 * (s - 1) * (s - 1) * d
    return float(in_proj + nl * layer + targets + infonce)
