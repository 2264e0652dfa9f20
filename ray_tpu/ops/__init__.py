"""ray_tpu.ops: TPU kernels (Pallas) and collective attention algorithms.

- flash_attention: fused causal attention forward (Pallas, VMEM-blocked
  online softmax) with a memory-bounded chunked backward.
- ring_attention: sequence-parallel attention over the 'sp' mesh axis —
  KV blocks rotate around the ICI ring via ppermute while each chip keeps
  its queries resident (SURVEY.md §5.7: absent in the reference; first-class
  here).
- ssd: the chunked state-space scan of a Mamba-2 mixer, forward and
  backward, its decay block in VMEM and its state carried between chunks.

Kernels run under `interpret=True` automatically on CPU (tests); compiled
Mosaic on TPU.
"""

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ssd import ssd_scan

__all__ = ["flash_attention", "ring_attention", "ssd_scan"]
