"""Seconds from process start until the window opened."""


def read(run):
    return run.setup_s
