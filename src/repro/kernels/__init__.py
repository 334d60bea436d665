"""Pallas TPU kernels for the rollout/training hot spots.

Each kernel: <name>.py (pl.pallas_call + BlockSpec VMEM tiling), validated
in interpret mode against the pure-jnp oracle in ref.py.  Callers pass
``interpret`` explicitly: nothing here falls back to interpret mode off the
chip.  tests/test_chip_compile.py compiles the paged decode kernel for a
described TPU v5e.
"""
from repro.kernels import ref  # noqa: F401
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.decode_attention import decode_attention  # noqa: F401
from repro.kernels.paged_decode_attention import paged_decode_attention  # noqa: F401
from repro.kernels.rwkv6_scan import rwkv6_scan  # noqa: F401
from repro.kernels.rglru_scan import rglru_scan  # noqa: F401
