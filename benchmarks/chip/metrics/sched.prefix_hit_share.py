"""Prompt tokens served from the prefix cache over the prompt tokens of
the requests sent in the window."""


def read(ctx):
    n = ctx["prompt_tokens_sent"]
    return ctx["counters"]["prefix_hit_tokens"] / n if n else None
