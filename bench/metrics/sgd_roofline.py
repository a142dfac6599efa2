"""``sgd_roofline``: percent of the HBM roofline reached by the
``train_glm`` programs of the traced window (``bench/roofline.py``)."""
from bench import roofline


def read(run):
    return roofline.share(run, "train_glm")
