"""Data: deterministic sparse-matrix generators."""
from . import graphs

__all__ = ["graphs"]
