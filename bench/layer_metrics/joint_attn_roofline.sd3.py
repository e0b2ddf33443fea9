"""The joint attention kernel's share of its roofline, in %: the least
time the attentions run in the window could take on the chip (4 S^2 D
operations and bf16 q/k/v plus f32 output bytes a row and block at the
real S = 4429, ``bench/flops_mmdit.py``) over the device time under
``mmdit.attn.core`` (``bench/scopes_mmdit.py``)."""

from bench import scopes_mmdit


def read(ctx):
    return scopes_mmdit.joint_attn_roofline(ctx)
