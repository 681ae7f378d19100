from .execution import (Capability, ExecutionModeError,
                        EXECUTION_MODES, execution_mode, get_execution_mode,
                        resolve_interpret, set_execution_mode)
from .fault_tolerance import TrainLoopRunner, StragglerMonitor

__all__ = [
    "Capability",
    "ExecutionModeError",
    "EXECUTION_MODES",
    "execution_mode",
    "get_execution_mode",
    "resolve_interpret",
    "set_execution_mode",
    "TrainLoopRunner",
    "StragglerMonitor",
]
