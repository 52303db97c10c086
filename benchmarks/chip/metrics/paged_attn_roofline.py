"""The paged-attention kernel's share of its roofline over the traced
steps: each call's least time (operations over peak or live pages read
over bandwidth, the larger) summed, over the kernel's summed device
time inside those steps, in percent."""

from chipbench import flops, trace


def read(ctx):
    pairs = ctx["pairs"]
    if ctx["peaks"] is None:
        return None
    ns = trace.kernel_ns_within(ctx["events"], [m for _, m in pairs])
    if not ns:
        return None
    pk = ctx["peaks"]
    bound = sum(flops.kernel_step_bound_s(
        ctx["model"], p.rows, ctx["block_size"], pk["bf16_flops"],
        pk["hbm_bytes_per_s"]) for p, _ in pairs)
    return 100.0 * bound / (ns / 1e9)
