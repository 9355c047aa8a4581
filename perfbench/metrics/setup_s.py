"""Set-up seconds: from process start (imports, chip, inputs, compiling or
loading every program, warm-up) to the window's first request."""


def read(run):
    return run.setup_s
