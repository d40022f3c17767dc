"""Set-up: process start to the window's start (host clock): start-up,
weights, programming, calibration, and every program this cell runs
compiled or loaded and run once."""


def read(run):
    return run.setup["setup_s"]
