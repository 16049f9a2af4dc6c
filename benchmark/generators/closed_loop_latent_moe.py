"""``closed_loop``, the generator the benchmark has, for the driver
``serve_latent_moe``: the same class and the same ``build`` (imported, not
copied). A generator states the driver it serves as ``KIND``, and this one's
driver is not ``serve``."""
from .closed_loop import ClosedLoop, build  # noqa: F401

KIND = "serve_latent_moe"
