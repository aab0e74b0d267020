"""Pallas TPU kernels.

They compile with Mosaic for the TPU (tests/test_tpu_compile.py compiles
the decode path's kernels for a described v5e at published widths, and
chip_smoke.py runs them on a chip).  On the CPU they run in Pallas
interpret mode (``interpret=True``), which the CPU tests use to check
them against their oracles; interpret mode is refused on a TPU.

Each subpackage: ``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit wrapper), ``ref.py`` (pure-jnp oracle).
"""
from repro.kernels.fused_decode.ops import fused_decode, rope_at  # noqa: F401
from repro.kernels.flash_decode.ops import flash_decode  # noqa: F401
from repro.kernels.fused_ffn.ops import fused_ffn  # noqa: F401
from repro.kernels.fused_head.ops import fused_head  # noqa: F401
from repro.kernels.fused_mla_decode.ops import fused_mla_decode  # noqa: F401
from repro.kernels.rglru_scan.ops import rglru_scan  # noqa: F401
from repro.kernels.rwkv6_scan.ops import rwkv6_scan  # noqa: F401
