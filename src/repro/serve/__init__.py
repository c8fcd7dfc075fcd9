"""Serving: the request-batching SpMM service front (bounded admission,
deadlines, quarantine — see ``SpmmService.health()``)."""
from . import spmm_service
from .spmm_service import ADMISSION_POLICIES, ServiceStats, SpmmService

__all__ = ["spmm_service", "ADMISSION_POLICIES", "ServiceStats", "SpmmService"]
