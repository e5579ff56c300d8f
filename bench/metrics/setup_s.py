"""Set-up: process start to the end of warm-up, compilation included."""


def read(run):
    return run.setup_s
