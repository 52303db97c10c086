"""The whole step's share of the chip's peak: operations the model needs
for the real rows of the traced steps (``flops.step_flops``) over their
summed device time times the bf16 peak, in percent."""

from chipbench import flops


def read(ctx):
    pairs = ctx["pairs"]
    if ctx["peaks"] is None:
        return None
    ns = sum(mod.dur_ns for _, mod in pairs)
    if not ns:
        return None
    work = sum(flops.step_flops(ctx["model"], p.rows) for p, _ in pairs)
    return 100.0 * work / (ns / 1e9 * ctx["peaks"]["bf16_flops"])
