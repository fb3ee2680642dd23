"""Operations and bytes of ResNet-50 training, from shapes."""

WIDTHS = (64, 128, 256, 512)


def convs(cfg, mix):
    """[(cin, cout, k, out_hw, fused)] of every convolution for one
    image; `fused` marks those followed by batch norm AND relu, which
    the program's fused kernel takes (the block's last conv and the
    projection are followed by the add first)."""
    s = mix["image"] // 2
    out = [(3, 64, 7, s, True)]
    s //= 2
    inp = 64
    for li, (planes, n) in enumerate(zip(WIDTHS, cfg["depths"])):
        for b in range(n):
            stride = 2 if (b == 0 and li > 0) else 1
            out.append((inp, planes, 1, s, True))
            s2 = s // stride
            out.append((planes, planes, 3, s2, True))
            out.append((planes, planes * 4, 1, s2, False))
            if b == 0:
                out.append((inp, planes * 4, 1, s2, False))
            inp, s = planes * 4, s2
    return out


def train_flops_per_sample(cfg, mix):
    f = sum(2 * ci * co * k * k * hw * hw for ci, co, k, hw, _ in
            convs(cfg, mix))
    return 3.0 * (f + 2 * WIDTHS[-1] * 4 * cfg["num_classes"])


def fused_conv_least_seconds(cfg, mix, peaks):
    """(number of fused triples, least seconds per step for them): each
    fused conv forward + backward needs 3 x its forward operations, and
    must at least read its input and write its output once each way in
    bfloat16; the larger of the two times, summed."""
    b, total, n = mix["batch"], 0.0, 0
    for ci, co, k, hw, fused in convs(cfg, mix):
        if not fused:
            continue
        n += 1
        flops = 3.0 * 2 * ci * co * k * k * hw * hw * b
        stride_in = hw * (2 if k == 7 else 1)
        nbytes = 2.0 * 2 * b * (ci * stride_in ** 2 + co * hw * hw)
        total += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return n, total
