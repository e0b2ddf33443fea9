"""Operations and bytes the algorithms need, counted from shapes.

A multiply-add is 2 operations. Only matrix products and convolutions are
counted (norms, activations and softmax are a few per element and left
out), so a share of peak computed from these is a floor of the true one.
"""

from __future__ import annotations


def conv_flops(h, w, k, cin, cout):
    return 2 * h * w * k * k * cin * cout


def attn_block_flops(n, c, text_len, text_dim):
    """Self- then cross-attention over ``n`` pixels of ``c`` channels."""
    self_ = 4 * 2 * n * c * c + 2 * 2 * n * n * c
    cross = 2 * 2 * n * c * c + 2 * 2 * text_len * text_dim * c \
        + 2 * 2 * n * text_len * c
    return self_ + cross


def res_block_flops(h, w, cin, cout, time_dim):
    f = conv_flops(h, w, 3, cin, cout) + conv_flops(h, w, 3, cout, cout) \
        + 2 * time_dim * cout
    if cin != cout:
        f += conv_flops(h, w, 1, cin, cout)
    return f


def unet_row_flops(cfg: dict) -> int:
    """One UNet forward for one latent (one row of the denoiser batch)."""
    ch = [cfg["base_channels"] * m for m in cfg["channel_mults"]]
    td, base, L, Dt = (cfg["time_dim"], cfg["base_channels"], cfg["text_len"],
                       cfg["text_dim"])
    attn_at = set(cfg["attn_resolutions"])
    s = cfg["latent_size"]
    f = 2 * (base * td + td * td)
    f += conv_flops(s, s, 3, cfg["in_channels"], ch[0])
    skips, cin = [ch[0]], ch[0]
    for lvl, c in enumerate(ch):
        r = s >> lvl
        for _ in range(cfg["num_res_blocks"]):
            f += res_block_flops(r, r, cin, c, td)
            if 2 ** lvl in attn_at:
                f += attn_block_flops(r * r, c, L, Dt)
            cin = c
            skips.append(c)
        if lvl < len(ch) - 1:
            f += conv_flops(r // 2, r // 2, 3, c, c)
            skips.append(c)
    r = s >> (len(ch) - 1)
    f += 2 * res_block_flops(r, r, cin, cin, td) + attn_block_flops(r * r, cin, L, Dt)
    for lvl, c in reversed(list(enumerate(ch))):
        r = s >> lvl
        for _ in range(cfg["num_res_blocks"] + 1):
            f += res_block_flops(r, r, cin + skips.pop(), c, td)
            if 2 ** lvl in attn_at:
                f += attn_block_flops(r * r, c, L, Dt)
            cin = c
        if lvl > 0:
            f += conv_flops(2 * r, 2 * r, 3, c, c)
    f += conv_flops(s, s, 3, cin, cfg["out_channels"])
    return f


def encoder_flops(tokens, dim, layers, ff):
    """Bidirectional encoder over ``tokens`` positions."""
    per = 2 * tokens * (4 * dim * dim + 2 * dim * ff) + 2 * 2 * tokens * tokens * dim
    return layers * per


def diffusion_image_flops(cfg: dict, steps: int, fraction: float) -> int:
    """The passes a selective plan requires for one image: two UNet rows on
    each FULL step, one on each COND step, plus the prompt's encoding."""
    n_cond = int(steps * fraction + 0.5)
    rows = 2 * (steps - n_cond) + n_cond
    t = cfg["text_encoder"]
    return rows * unet_row_flops(cfg) + encoder_flops(
        cfg["text_len"], cfg["text_dim"], t["layers"], t["ff"])

