"""Device idle time in the traced window per step program run in it."""
import readers


def read(ctx):
    r = ctx.get("reduced")
    runs = readers.program_runs(ctx, with_kernels=True)
    if not r or not runs:
        return None
    idle_s = r["window_s"] - min(r["busy_s_per_device"])
    return 1e3 * idle_s / len(runs)
