"""prefill_chunk_ms.longgen: see ``bench/readers.py``."""

from bench.readers import prefill_chunk_ms as read  # noqa: F401
