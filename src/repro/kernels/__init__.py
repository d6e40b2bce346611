"""Pallas TPU kernels for the RMQ hot spots (+ ops wrappers, ref oracles)."""

from . import ops, ref
from .fused_query import fused_query
from .rmq_query import rmq_partials

__all__ = ["ops", "ref", "fused_query", "rmq_partials"]
