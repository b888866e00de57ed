"""Grouped matrix multiplication: the dropless expert layer's products over
exactly the rows routed to each expert."""

from repro.kernels.gmm.ops import gmm

__all__ = ["gmm"]
