"""Pallas TPU kernels for the scoring hot path."""

from linkerd_tpu.ops.scoring import fused_anomaly_scores

__all__ = ["fused_anomaly_scores"]
