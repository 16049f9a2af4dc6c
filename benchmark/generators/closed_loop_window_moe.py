"""``closed_loop``, the generator the benchmark has, for the driver
``serve_window_moe``: the same class and the same ``build`` (imported, not
copied). The driver hands it the configuration's vocabulary SLICE as
``vocab_size``, so the ids are drawn from the slice."""
from .closed_loop import ClosedLoop, build  # noqa: F401

KIND = "serve_window_moe"
