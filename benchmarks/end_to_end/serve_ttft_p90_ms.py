"""90th percentile, over every request due in the window, of first token
handed out minus the moment the request was due."""
import readers


def read(ctx):
    return readers.percentile(readers.ttfts_ms(ctx), 90)
