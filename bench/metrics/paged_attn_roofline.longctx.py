"""paged_attn_roofline.longctx: see ``bench/readers.py``."""

from bench.readers import paged_attn_roofline as read  # noqa: F401
