"""``idle_serve_share.train``: percent of the traced window in which the
device ran no operation while the host was inside one of the program's
``serve.*`` spans (submit, drain) and no deeper program span
(``bench/program_trace.py``).  With the exec, trainer and client
shares it adds up to ``idle_share.train``."""
from bench import program_trace


def read(run):
    return program_trace.idle_share(run, "serve")
