"""The paper's contribution as a composable library.

channels   — channel-aware placement planning (+ the Fig. 2 bandwidth model)
shim       — lane/VMEM block planning (the HBM-shim analogue)
selection  — scale-out range selection (paper §IV)
join       — scale-out naively-partitioned hash join (paper §V)
sgd_glm    — scale-out GLM training / hyper-parameter search (paper §VI)
"""
from repro.core import channels, join, selection, sgd_glm, shim

__all__ = ["channels", "join", "selection", "sgd_glm", "shim"]
