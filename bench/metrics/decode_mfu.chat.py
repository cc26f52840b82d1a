"""decode_mfu.chat: see ``bench/readers.py``."""

from bench.readers import decode_mfu as read  # noqa: F401
