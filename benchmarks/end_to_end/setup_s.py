"""Process start to the moment the window opens: load, the checked
sample, warm-up, the ramp and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
