"""Plain float32 forwards, one module per model family.

Independent of ``deepspeed_tpu.models``: straightforward ``jax.numpy``
from the published description, no kernels, no cache, no batching.  On a
TPU a float32 product runs in lower precision unless asked otherwise, so
every entry point here runs under ``precision="highest"``.
"""
