"""All tokens of all optimizer steps finished in the window, over the
window's seconds, over the chips."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return (ctx["steps"] * ctx["tokens_per_step"] / ctx["window_s"]
            / ctx["chips"])
