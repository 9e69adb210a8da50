from .compile_cache import enable_compile_cache
from .fault import ResumableLoop, StragglerMonitor, elastic_remesh
from .tracing import scope, span

__all__ = [
    "ResumableLoop",
    "StragglerMonitor",
    "elastic_remesh",
    "enable_compile_cache",
    "scope",
    "span",
]
