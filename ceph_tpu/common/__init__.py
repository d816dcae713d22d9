from .config import Option, ConfigProxy, OPT_INT, OPT_STR, OPT_FLOAT, \
    OPT_BOOL, OPT_DOUBLE
from .perf_counters import (
    PerfCounters, PerfCountersBuilder, PerfCountersCollection,
)
from .admin_socket import AdminSocket
from .tracked_op import OpTracker, TrackedOp
from .lockdep import (DebugLock, DebugRLock, LockOrderError,
                      lockdep_enable, lockdep_reset)
from .dout import Dout, Log, dlog, get_log, register_config_observers
from .kernel_trace import KernelTimer, g_kernel_timer

__all__ = [
    "Option", "ConfigProxy", "OPT_INT", "OPT_STR", "OPT_FLOAT", "OPT_BOOL",
    "OPT_DOUBLE", "PerfCounters", "PerfCountersBuilder",
    "PerfCountersCollection", "AdminSocket", "OpTracker", "TrackedOp",
    "DebugLock", "DebugRLock", "LockOrderError", "lockdep_enable",
    "lockdep_reset",
    "Dout", "Log", "dlog", "get_log", "register_config_observers",
    "KernelTimer", "g_kernel_timer",
]
