"""Utility subsystems: metrics, structured logging, profiling."""

from ps_tpu.utils.metrics import Meter, TrainMetrics
from ps_tpu.utils.step_log import StepLogger
from ps_tpu.utils.profiling import trace

__all__ = ["Meter", "TrainMetrics", "StepLogger", "trace"]
