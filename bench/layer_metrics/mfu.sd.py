"""Share of the chip's bf16 peak that the plan's required UNet passes and
prompt encodings reach: operations per image x images / window / peak."""


def read(ctx):
    c, tr = ctx["counters"], ctx["trace"]
    if not c.get("images") or not tr["window_s"]:
        return None
    rate = c["image_flops"] * c["images"] / tr["window_s"]
    return 100.0 * rate / (ctx["peaks"]["flops_bf16"] * ctx["chips"])
