"""Reproduction of "High Bandwidth Memory on FPGAs: A Data Analytics
Perspective" on a JAX mesh.
"""
