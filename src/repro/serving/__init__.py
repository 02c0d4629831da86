"""Serving layer: the HcPE batch query front-end (hcpe.py, DESIGN.md
§4), the async deadline-aware HcPE front-end (async_server.py,
DESIGN.md §7), the tenant-graph registry behind both HcPE front-ends
(registry.py, DESIGN.md §8 — now also the streaming-mutation and
live-quota write path, §12), and the metrics control plane (metrics.py,
DESIGN.md §12).  The public surface is documented in the README "API
reference" section."""

from .async_server import AsyncHcPEServer, AsyncServeStats
from .hcpe import (BatchServeReport, HcPEServer, PathQueryRequest,
                   PathQueryResponse, STATUS_OK, STATUS_REJECTED_QUEUE_FULL,
                   STATUS_REJECTED_QUOTA, STATUS_REJECTED_SHUTDOWN,
                   STATUS_REJECTED_NO_WEIGHTS, STATUS_REJECTED_TENANT_QUOTA,
                   STATUS_REJECTED_UNKNOWN_GRAPH)
from .metrics import MetricsSnapshot, TenantMetrics, snapshot
from .registry import GraphRegistry, TenantEntry

__all__ = ["HcPEServer", "PathQueryRequest", "PathQueryResponse",
           "BatchServeReport", "AsyncHcPEServer", "AsyncServeStats",
           "GraphRegistry", "TenantEntry",
           "MetricsSnapshot", "TenantMetrics", "snapshot",
           "STATUS_OK", "STATUS_REJECTED_QUEUE_FULL", "STATUS_REJECTED_QUOTA",
           "STATUS_REJECTED_TENANT_QUOTA", "STATUS_REJECTED_UNKNOWN_GRAPH",
           "STATUS_REJECTED_SHUTDOWN", "STATUS_REJECTED_NO_WEIGHTS"]
