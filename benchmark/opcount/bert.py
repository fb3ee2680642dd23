"""Operations and bytes of BERT pretraining, from shapes."""


def train_flops_per_sample(cfg, mix):
    """Forward + backward (3 x forward) matmul operations of one
    sequence: per layer the four projections, the two attention matmuls
    and the MLP; the MLM head on the masked positions; the pooler."""
    h, f, s = cfg["hidden_size"], cfg["intermediate_size"], mix["seq"]
    layer = 8 * s * h * h + 4 * s * s * h + 4 * s * h * f
    head = mix["masked"] * (2 * h * h + 2 * h * cfg["vocab_size"])
    return 3.0 * (cfg["num_hidden_layers"] * layer + head + 2 * h * h + 4 * h)

