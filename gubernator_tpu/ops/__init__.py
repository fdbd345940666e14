"""Device-side ops: the HBM slot table and the vectorized decide kernel.

All counter math is int64; jax x64 mode is enabled at import. (This package
is a rate limiter, not an ML trainer — there is no f32 ML math to slow
down, and epoch-millisecond timestamps require 64-bit integers.)
"""

import jax

jax.config.update("jax_enable_x64", True)

from gubernator_tpu.ops.layout import SlotTable, RequestBatch, DecideOutput  # noqa: E402

__all__ = [
    "SlotTable",
    "RequestBatch",
    "DecideOutput",
]
