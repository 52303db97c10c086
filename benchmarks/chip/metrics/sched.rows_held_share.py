"""Share of the decode-phase rows a step could have carried that it left
out because another worker's step held them, from the scheduler's
counters."""


def read(ctx):
    c = ctx["counters"]
    if "decode_rows_held" not in c:
        return None
    seen = c["decode_rows"] + c["decode_rows_held"]
    return c["decode_rows_held"] / seen if seen else None
