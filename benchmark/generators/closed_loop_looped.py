"""``closed_loop``, the generator the benchmark has, for the driver
``serve_looped``: the same class and the same ``build`` (imported, not
copied)."""
from .closed_loop import ClosedLoop, build  # noqa: F401

KIND = "serve_looped"
