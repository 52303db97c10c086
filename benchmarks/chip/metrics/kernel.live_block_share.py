"""Share of the paged kernel's grid that holds context: the real rows'
live KV blocks over the blocks walked (dispatched rows x table width),
from the engine's counters."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("kv_blocks_walked"):
        return None
    return c["kv_blocks_live"] / c["kv_blocks_walked"]
