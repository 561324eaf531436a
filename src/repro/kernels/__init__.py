"""The fused whole-chip allocate: heSRPT ranks -> Thm-7 brackets -> chips.

``alloc.py`` holds the Pallas kernel (no sorts: positions from comparison
counting) and its pure-jnp reference.  ``engine.quantized_rule`` and
``engine.continuous_rule`` attach it to heSRPT rules as ``fused_variant``;
``engine.run(fused=True)`` and ``Sweep(fused=True)`` select it.
"""

from repro.kernels import alloc

__all__ = ["alloc"]
