"""setup_s: seconds from process start to the window (imports, scenario
build, consts, program loads or compiles, warm-up units)."""


def read(ctx):
    return ctx["setup_s"]
