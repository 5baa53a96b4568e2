"""Core utilities layer (reference: include/dmlc/{logging,registry,parameter,
config,serializer,timer}.h)."""

from dmlc_tpu.utils.logging import (
    DMLCError, check, check_eq, check_ne, check_lt, check_le, check_gt,
    check_ge, check_notnone, log_info, log_warning, log_error, log_fatal,
    set_log_sink,
)
from dmlc_tpu.utils.registry import Registry
from dmlc_tpu.utils.parameter import Parameter, field, get_env, ParamError
from dmlc_tpu.utils.config import Config
from dmlc_tpu.utils.timer import get_time
from dmlc_tpu.utils.concurrency import (
    ConcurrentBlockingQueue, PriorityBlockingQueue,
)
from dmlc_tpu.utils.thread_group import (
    ManualEvent, ThreadGroup, ThreadLocalStore,
)
from dmlc_tpu.utils.memory import BufferPool, thread_local_pool

__all__ = [
    "DMLCError", "check", "check_eq", "check_ne", "check_lt", "check_le",
    "check_gt", "check_ge", "check_notnone", "log_info", "log_warning",
    "log_error", "log_fatal", "set_log_sink", "Registry", "Parameter",
    "field", "get_env", "ParamError", "Config", "get_time",
    "ConcurrentBlockingQueue", "PriorityBlockingQueue", "ManualEvent",
    "ThreadGroup", "ThreadLocalStore", "BufferPool", "thread_local_pool",
]
