"""``mfu.train``: percent of the chip's peak bf16 FLOP/s that the
``train_glm`` calls of the traced window reached over the whole window
(``bench/roofline.py``)."""
from bench import roofline


def read(run):
    return roofline.mfu(run, "train_glm")
