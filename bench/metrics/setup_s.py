"""setup_s: process start to the window's start (imports, the chip's
start-up, compiles, on-slot weight inits, warm-up), host clock."""


def read(run):
    return run.setup_s
