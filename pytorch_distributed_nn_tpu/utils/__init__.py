"""Utilities: metrics instrumentation."""

from pytorch_distributed_nn_tpu.utils.timing import MetricsLogger

__all__ = ["MetricsLogger"]
