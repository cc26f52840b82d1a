"""host_ms_per_tick.chat: see ``bench/readers.py``."""

from bench.readers import host_ms_per_tick as read  # noqa: F401
