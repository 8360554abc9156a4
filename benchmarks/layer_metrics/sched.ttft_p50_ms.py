"""Median time to first token, from when the request was due."""
import readers


def read(ctx):
    return readers.percentile(readers.ttfts_ms(ctx), 50)
