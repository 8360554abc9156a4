"""All output tokens handed out in the window over the window's seconds."""


def read(ctx):
    n = ctx["book"].window_tokens
    return n / ctx["window_s"] if n else None
