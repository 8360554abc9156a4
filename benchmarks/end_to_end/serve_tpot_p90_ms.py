"""90th percentile over every request finished in the window of
(last token - first token) / (output tokens - 1)."""
import readers


def read(ctx):
    return readers.percentile(readers.tpots_ms(ctx), 90)
