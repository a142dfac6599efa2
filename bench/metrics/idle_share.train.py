"""``idle_share.train``: percent of the traced window in which the device
ran no operation (``bench/roofline.py``)."""
from bench import roofline


def read(run):
    return roofline.idle(run)
