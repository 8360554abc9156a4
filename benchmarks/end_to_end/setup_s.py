"""Process start to the first measured instant: loading, warming up, the
ramp of an open-loop mix and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
