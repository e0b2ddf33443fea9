"""Stable Diffusion 3 Medium's MMDiT (arXiv:2403.03206), at the published
config (stabilityai/stable-diffusion-3-medium-diffusers,
``transformer/config.json``): 24 joint blocks of 24 heads x 64 (hidden
1536), 2x2 patches of 128x128x16 latents, a 4096-wide text context of
77 CLIP + 256 T5 tokens, pooled 2048. Its scheduler is the shift-3 flow
matching of ``FlowSchedule.sd3_default``; the pipeline's defaults are 28
steps at guidance 7.0.
"""

from repro.configs.base import MMDiTConfig

CONFIG = MMDiTConfig()
