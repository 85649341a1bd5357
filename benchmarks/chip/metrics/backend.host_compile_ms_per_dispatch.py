"""Host milliseconds in JAX's trace, lower and compile-or-cache-load path
during the window, per dispatch to a backend."""
from readings import compile_ms_per_dispatch as read  # noqa: F401
