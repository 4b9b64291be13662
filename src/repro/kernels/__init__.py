"""Pallas TPU kernels for ColoGrid's compute hot-spots.

Each kernel package ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM
tiling), ``ops.py`` (jit'd public wrapper, shape plumbing, interpret-mode
switch) and ``ref.py`` (pure oracle used by the allclose sweeps):

- ``fused_fold``       — the fold-phase workhorse: one HBM pass per block
  emitting the grouped CSE shared-accumulator pool
  ``(count, Σx, Σx², Σx³, Σx⁴)`` per group, fp32 in VMEM;
- ``streaming_stats``  — the paper's map-task hot loop: masked streaming
  sum/count (+ second moment) over a chunk of image rows (ANTS
  AverageImages analogue, HBM-bandwidth-bound).  Since the fused fold
  kernel landed it is a thin facade over ``fused_fold`` with the
  ``(Σx, Σx², n)`` accumulator subset;
- ``flash_attention``  — blockwise softmax attention forward (training /
  prefill path of the LM workloads);
- ``ssm_scan``         — chunked SSD recurrence (mamba2 / zamba2 / long
  context decode).

Kernels target the TPU (tile sizes chosen for VMEM and the 128×128 MXU)
and compile by default.  CPU runs and tests validate them with an explicit
``interpret=True`` (``fold_interpret=True`` at the session level).
"""
