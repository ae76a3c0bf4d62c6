"""TPU Pallas kernels for the framework's compute hot spots.

Three kernels, each a subpackage with:
  kernel.py — pl.pallas_call body + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, reshapes, interpret switch)
  ref.py    — pure-jnp oracle used by the per-kernel allclose test sweeps

  dls_chunks       the paper's chunk calculation, TPU-vectorized: closed-form
                   chunk sizes for a tile of scheduling steps + carried
                   prefix-sum assignment (DESIGN.md Sec. 2)
  flash_attention  blocked online-softmax attention (causal / sliding-window /
                   GQA) — the LM stack's dominant FLOP consumer
  mamba_scan       chunked selective-scan for Mamba blocks (falcon-mamba,
                   jamba) — sequential grid over sequence chunks with the SSM
                   state carried in VMEM scratch

Off a TPU the kernels run through the Pallas interpreter, which the CPU
tests use; tests/test_tpu_compile.py compiles them for a described v5e, and
chip_smoke.py runs the dls_chunks kernel on the chip.  BlockSpecs are shaped
for v5e VMEM/MXU (128-aligned tiles).
"""

from . import dls_chunks, flash_attention, mamba_scan  # noqa: F401
