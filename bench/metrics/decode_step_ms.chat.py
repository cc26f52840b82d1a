"""decode_step_ms.chat: see ``bench/readers.py``."""

from bench.readers import decode_step_ms as read  # noqa: F401
