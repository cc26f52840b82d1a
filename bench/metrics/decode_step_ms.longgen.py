"""decode_step_ms.longgen: see ``bench/readers.py``."""

from bench.readers import decode_step_ms as read  # noqa: F401
