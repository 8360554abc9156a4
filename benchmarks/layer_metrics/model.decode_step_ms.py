"""Device time of one decode step: the time of the programs that hold the
decode kernel over the steps they ran (kernel calls / layers)."""
import readers


def read(ctx):
    runs = readers.program_runs(ctx, with_kernels=True)
    steps = readers.kernel_count(ctx) / ctx["config"]["num_hidden_layers"]
    if not runs or not steps:
        return None
    return 1e-6 * sum(d for _, d in runs) / steps
