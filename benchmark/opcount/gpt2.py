"""Operations and bytes of GPT-2 decoding, from shapes."""


def param_count(cfg):
    h, v, p, nl = (cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"],
                   cfg["n_layer"])
    return v * h + p * h + nl * (12 * h * h + 13 * h) + 2 * h


def kv_bytes_per_token(cfg):
    return 2 * cfg["n_layer"] * cfg["n_embd"] * 4  # K and V, float32


def decode_bytes(cfg, live_tokens):
    """Least bytes of one decode step: every float32 weight once (the
    position table aside) and the cached K and V of the tokens the live
    slots hold."""
    weights = 4 * (param_count(cfg) - cfg["n_positions"] * cfg["n_embd"])
    return weights + kv_bytes_per_token(cfg) * live_tokens


def decode_flops(cfg, slots):
    return 2.0 * param_count(cfg) * slots
