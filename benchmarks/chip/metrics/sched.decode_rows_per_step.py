"""Real decode rows a step carries, over the steps that carry decode rows
(decode plans and mixed plans), from the scheduler's counters."""


def read(ctx):
    c = ctx["counters"]
    if "decode_rows" not in c:
        return None
    steps = c["steps"] - c["prefill_chunks"] + c["mixed_steps"]
    return c["decode_rows"] / steps if steps else None
