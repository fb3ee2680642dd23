"""Operations and bytes of the `minicpm_sala` serving cut, from shapes
(and, for the traced run's readers, which device events are whose): one
pipeline stage of eight whole layers. bfloat16 weights, K/V and pooled
rings (2 bytes), float32 recurrent state (4 bytes). `cfg["mixer_types"]`
says what each layer is: `minicpm4` block-sparse grouped-query attention
(sizes in `cfg["sparse_config"]`), `lightning-attn` the linear
recurrence; every layer has a dense SwiGLU. How the program blocks a
prompt (`cfg["blocking"]`: a sparse layer's queries and keys at a time,
a Lightning layer's chunk) is in the configuration file, which
`build.py` hands to the program: the predicates below read the same
numbers and copy none."""

import re


def _n(cfg):
    kinds = cfg["mixer_types"]
    sc = cfg["sparse_config"]
    return dict(
        h=cfg["hidden_size"], v=cfg["vocab_size"], f=cfg["intermediate_size"],
        hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
        d=cfg["head_dim"], q=cfg["num_attention_heads"] * cfg["head_dim"],
        kv=cfg["num_key_value_heads"] * cfg["head_dim"],
        lh=cfg["lightning_nh"], ld=cfg["lightning_head_dim"],
        lq=cfg["lightning_nh"] * cfg["lightning_head_dim"],
        layers=len(kinds), sparse=kinds.count("minicpm4"),
        lightning=kinds.count("lightning-attn"),
        kernel=sc["kernel_size"], stride=sc["kernel_stride"],
        block=sc["block_size"], topk=sc["topk"], dense_len=sc["dense_len"],
        qb=cfg["blocking"]["sparse_q_block"],
        kc=cfg["blocking"]["sparse_key_chunk"],
        lc=cfg["blocking"]["lightning_chunk"])


def mixer_params(cfg, kind):
    """Parameters of one mixer (its q/k/output gains included)."""
    n = _n(cfg)
    if kind == "minicpm4":
        return 3 * n["h"] * n["q"] + 2 * n["h"] * n["kv"] + 2 * n["d"]
    return 5 * n["h"] * n["lq"] + 2 * n["ld"] + n["lq"]


def layer_params(cfg, kind):
    """A mixer, its layer's SwiGLU and the two norms' gains."""
    n = _n(cfg)
    return mixer_params(cfg, kind) + 3 * n["h"] * n["f"] + 2 * n["h"]


def dense_params(cfg):
    """Everything a decode step reads: every layer, the final norm, the
    head (the embedding is read one row a token)."""
    n = _n(cfg)
    return (n["sparse"] * layer_params(cfg, "minicpm4")
            + n["lightning"] * layer_params(cfg, "lightning-attn")
            + n["h"] + n["h"] * n["v"])


def param_count(cfg):
    n = _n(cfg)
    return dense_params(cfg) + n["v"] * n["h"]


def state_bytes_per_slot(cfg, tail=True):
    """The Lightning layers' float32 states (there is no tail)."""
    n = _n(cfg)
    return n["lightning"] * n["lh"] * n["ld"] * n["ld"] * 4


def kv_row_bytes(cfg):
    """A K and a V row of one sparse layer (a pooled row is half one)."""
    return 2 * _n(cfg)["kv"] * 2


def kv_bytes_per_token(cfg):
    """What one more token costs a slot: a K/V row and 1 / stride of a
    pooled row in every sparse layer."""
    n = _n(cfg)
    return n["sparse"] * (kv_row_bytes(cfg)
                          + kv_row_bytes(cfg) // (2 * n["stride"]))


def sparse_rows(cfg, live):
    """K/V rows (a pooled row counts half) that steps 1-6 read for one
    slot and layer holding ``live`` rows: all of them under `dense_len`;
    else `topk` blocks (the newest as far as the step's own position)
    and the pooled rows that exist. `generation/cache.py`
    `SparseKVKind.rows_read`, from shapes."""
    n = _n(cfg)
    live = max(int(live), 1)
    t = live - 1
    if live < n["dense_len"]:
        return live
    blocks = min(t // n["block"] + 1, n["topk"])
    pooled = (t - (n["kernel"] - 1)) // n["stride"] + 1
    return (blocks - 1) * n["block"] + t % n["block"] + 1 + (pooled + 1) // 2


def sparse_rows_bytes(cfg, live_tokens, slots):
    """Bytes of ring rows a decode step's sparse layers have to read
    when ``slots`` slots hold ``live_tokens`` rows between them (each
    taken at the mean)."""
    n = _n(cfg)
    return (n["sparse"] * slots * sparse_rows(cfg, live_tokens / max(slots, 1))
            * kv_row_bytes(cfg))


def decode_bytes(cfg, live_tokens, slots=None):
    """Least bytes of one decode step: the weights, every slot's states
    read and written, and the ring rows the selection names (NOT the
    rings whole: 64 blocks and the pooled keys a slot past
    `dense_len`)."""
    slots = cfg["engine"]["slots"] if slots is None else slots
    return (2 * dense_params(cfg) + 2 * slots * state_bytes_per_slot(cfg)
            + sparse_rows_bytes(cfg, live_tokens, slots))


def decode_flops(cfg, slots):
    """Two operations a parameter a token."""
    return 2.0 * dense_params(cfg) * slots


def _pooled_lengths(cfg):
    """Pooled rows of the decode ring and of each prefill bucket."""
    e, stride = cfg["engine"], cfg["sparse_config"]["kernel_stride"]
    return sorted({e["cache_len"] // stride}
                  | {b // stride for b in e["prefill_buckets"]})


def _alt(values):
    return "(" + "|".join(str(v) for v in values) + ")"


def is_state_op(text, cfg):
    """A device event whose instruction reads or writes a float32 tensor
    of the Lightning state's shape `[rows, heads, 128, 128]` (the decode
    step's pass over every slot's state, the admission's write of one,
    a prompt's state between chunks) or of the chunked prefill's chunk
    shapes (`blocking.lightning_chunk` tokens: the chunk x chunk decay
    and scores, a chunk's q, k, v and outputs a head)."""
    n = _n(cfg)
    lh, ld, lc = n["lh"], n["ld"], n["lc"]
    return any(re.search(p, text) for p in (
        rf"f32\[(\d+,)*{lh},{ld},{ld}\]",
        rf"f32\[(\d+,)*{lh},{lc},{lc}\]",
        rf"f32\[(\d+,)*{lh},{lc},{ld}\]"))


def is_sparse_select_op(text, cfg):
    """A device event of the selection or of the pooled ring's update:
    its instruction touches the pooled ring `[rows, 2, J, 128]`, the
    selection's scores `[rows, 2, 16, (queries,) J]`, its validity mask
    `[rows, 2, (queries,) J]`, or the block scores and their sorts
    `[rows, 2, (queries,) J / 4 (, 4)]`, `J` the pooled rows of the
    decode ring or of a prefill bucket and `queries` 1 or a prompt's
    `blocking.sparse_q_block`; or the 32 ring rows a decode step pools,
    `[rows, 2, 32, 128]`."""
    n = _n(cfg)
    hkv, g, d, qb = n["hkv"], n["hq"] // n["hkv"], n["d"], n["qb"]
    js = _pooled_lengths(cfg)
    per = n["block"] // n["stride"]
    j, nb = _alt(js), _alt([x // per for x in js])
    return any(re.search(p, text) for p in (
        rf"\[\d+,{hkv},{j},{d}\]",
        rf"f32\[\d+,{hkv},{g},(\d+,)?{j}\]",
        rf"(f32|pred|s32)\[\d+,{hkv},(1,|{qb},)?{j}\]",
        rf"(f32|pred|s32)\[\d+,{hkv},(1,|{qb},)?{nb}(,{per})?\]",
        rf"\[\d+,{hkv},{n['kernel']},{d}\]"))


def is_sparse_attend_op(text, cfg):
    """A device event of the block attention: in a decode step the
    gathered blocks `[slots, 2, M, 64, 128]` (M = the blocks gathered a
    slot, `nn.SparseConfig.gather_blocks`; the gather itself writes them
    as `[slots x 2 x M, 64, 128]`: my chip run, PR 49) and the scores
    over them
    `[slots, 2, 16, M, 64]` / `[slots, 2, 16, 64 M]`; in a prompt a
    query block's scores against a key chunk `[1, 2, 16, q, keys]`
    (`blocking.sparse_q_block`, `blocking.sparse_key_chunk`: 512,
    2,048) and its running maxima, sums and outputs `[1, 2, 16, q, 1 |
    128]`."""
    n = _n(cfg)
    hkv, g, d, blk = n["hkv"], n["hq"] // n["hkv"], n["d"], n["block"]
    qb, kc = n["qb"], n["kc"]
    m = max(n["topk"], -(-n["dense_len"] // blk))
    rows = cfg["engine"]["slots"] * hkv * m
    return any(re.search(p, text) for p in (
        rf"\[\d+,{hkv},{m},{blk},{d}\]",
        rf"\[{rows},{blk},{d}\]",
        rf"\[\d+,{hkv},{g},{m},{blk}\]",
        rf"\[\d+,{hkv},{g},{m * blk}\]",
        rf"\[\d+,{hkv},{g},{qb},{kc}\]",
        rf"f32\[\d+,{hkv},{g},{qb},(1|{d})\]"))
