"""Paged-attention kernel: least time at the chip's peaks for the work of
the traced steps (valid context only) over the kernel's device time, in
percent."""
from bench.core.readers import attn_roofline as read  # noqa: F401
