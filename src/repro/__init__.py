"""FeatureBox reproduction: GPU feature engineering + pipelined training.

Subpackages without an ``__init__`` (``launch``, ``models``, ``train``, ...)
remain importable as namespace portions.
"""
