"""The paper's system: the semi-centralized scheduler (``center``), the
SPMD superstep engine and its host driver, task frontier and spill tiers,
task codecs, and the asynchronous protocol simulation it is checked against.
"""
