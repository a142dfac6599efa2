"""``setup_s``: from the harness's first line to the window's start:
importing, making the tables, registering them, and warming every shape
the window uses (compiling, in a checkout's first run)."""


def read(run):
    return run.setup_s
