"""Distribution: the 1-D mesh and spec helpers of the sharded executor."""
from . import sharding

__all__ = ["sharding"]
