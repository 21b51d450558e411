"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel subpackage follows the required structure:
  <name>/kernel.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
  <name>/ops.py     — jit'd public wrapper (layout handling, interpret switch)
  <name>/ref.py     — pure-jnp oracle used by the allclose sweep tests

Kernels (DESIGN.md S3):
  flash_attention — blockwise online-softmax attention (causal / sliding
                    window / soft-cap / GQA); MXU-tiled.
  selective_scan  — Mamba-1 chunked selective scan, VMEM-resident state.
  ckpt_codec      — int8 block quantize/dequantize (checkpoint & gradient
                    compression: the paper-aligned kernel, shrinks the
                    Young/Daly C term).
  rmsnorm         — fused RMSNorm.
  abft_matmul     — checksum-extended matmul (Huang/Abraham ABFT): detects
                    and corrects a single corrupted output element; the
                    tier-1 SDC guard (docs/sdc.md).
  block_hash      — per-block uint32 mod-2^32 word sums: the dirty-block
                    detector behind incremental (delta) checkpoints AND the
                    SDC scrubber's leaf checksums (one reduction idiom,
                    two consumers — docs/checkpointing.md, docs/sdc.md).

Checked two ways without a chip: every kernel against its oracle in
interpret mode on CPU (tests/test_kernels.py), and every main-path kernel
compiled for a described v5e chip at published widths, asserting the
lowering is a Mosaic ``tpu_custom_call`` (tests/test_chip_compile.py).
``chip_smoke.py`` runs block_hash and abft_matmul compiled on a real chip.
"""
