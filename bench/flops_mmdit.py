"""Operations and bytes of SD3's MMDiT, counted from the configuration's
shapes at the real token counts (4096 image + 333 text), not the padded
ones the attention kernel runs. As in ``bench/flops.py``: a multiply-add
is 2 operations, and only matrix products count.
"""

from __future__ import annotations

from bench import flops as FL
from bench.reference.mmdit import TIME_DIM, TOWERS


def tokens(cfg: dict) -> tuple[int, int]:
    """(image tokens, text tokens) of one row."""
    return (cfg["sample_size"] // cfg["patch_size"]) ** 2, cfg["clip_len"] + cfg["t5_len"]


def hidden(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["attention_head_dim"]


def joint_attn_flops(cfg: dict) -> int:
    """QK^T and PV of one joint attention over one row: 4 S^2 D."""
    n, m = tokens(cfg)
    return 4 * (n + m) ** 2 * hidden(cfg)


def joint_attn_bytes(cfg: dict) -> int:
    """The least the kernel moves for one row: bf16 q, k and v in, the f32
    output out, S x D each."""
    n, m = tokens(cfg)
    return (n + m) * hidden(cfg) * (2 + 2 + 2 + 4)


def mmdit_row_flops(cfg: dict) -> int:
    """One MMDiT forward for one latent (one row of the denoiser batch)."""
    n, m = tokens(cfg)
    D, p = hidden(cfg), cfg["patch_size"]
    f = 2 * n * p * p * cfg["in_channels"] * D                     # patch embed
    f += 2 * (TIME_DIM * D + D * D) + 2 * (cfg["pooled_projection_dim"] * D + D * D)
    f += 2 * m * cfg["joint_attention_dim"] * cfg["caption_projection_dim"]
    for i in range(cfg["num_layers"]):
        pre_only = i == cfg["num_layers"] - 1
        f += 2 * D * 6 * D + 2 * D * (2 if pre_only else 6) * D  # modulations
        f += 2 * (n + m) * D * 3 * D + joint_attn_flops(cfg)
        out_rows = n if pre_only else n + m
        f += 2 * out_rows * D * D + 2 * out_rows * 2 * D * 4 * D  # out, MLP
    f += 2 * D * 2 * D + 2 * n * D * p * p * cfg["out_channels"]  # final
    return f


def image_flops(cfg: dict, steps: int, fraction: float) -> int:
    """The passes a selective plan requires for one image: two MMDiT rows
    on each FULL step, one on each COND step, plus the prompt's encoding
    by the three towers."""
    n_cond = int(steps * fraction + 0.5)
    rows = 2 * (steps - n_cond) + n_cond
    t = cfg["text_encoders"]
    enc = sum(FL.encoder_flops(cfg["t5_len"] if name == "t5" else cfg["clip_len"],
                               w, t["layers"][name], ff)
              for name, (w, _, ff) in TOWERS.items())
    return rows * mmdit_row_flops(cfg) + enc
