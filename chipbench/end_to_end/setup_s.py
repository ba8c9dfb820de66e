"""Process start (the first line of run.py) to the first timed window:
imports, program build, startup program, weights and inputs from the seed,
trace + lower + compile or cache read, warm-up of the cell's own shapes."""


def compute(ctx):
    return ctx.setup_s
