"""``idle_trainer_share.train``: percent of the traced window in which
the device ran no operation while the host was inside one of the
program's ``trainer.*`` spans: staging a morsel, or a step's dispatch
with its trace, lowering and compile or cache load
(``bench/program_trace.py``).  With the serve, exec and client shares
it adds up to ``idle_share.train``."""
from bench import program_trace


def read(run):
    return program_trace.idle_share(run, "trainer")
