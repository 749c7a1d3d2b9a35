"""Distribution: sharding rules, jet staged collectives, compression."""
from .sharding import ParallelCtx, single_device_ctx

__all__ = ["ParallelCtx", "single_device_ctx"]
