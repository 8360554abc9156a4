"""Tokens emitted by decode steps over slot-steps dispatched (``cb.stats``):
what is left goes to empty slots, prompts teacher-forced inside blocks and
steps past a retirement."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("slot_steps"):
        return None
    return 100.0 * (c["emitted_tokens"] - c["batch_admissions"]) / c["slot_steps"]
