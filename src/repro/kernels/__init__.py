"""Pallas TPU kernels for the communication hot spots.

Each kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper in
``ops.py``; all are validated on CPU with ``ops.interpret_params()``
(``pltpu.InterpretParams``, which simulates VMEM, DMA, remote copies and
semaphores) and compiled for a described TPU v5e topology by
``tests/test_chip_compile.py``.

Paper mapping:
  ring_allgather_matmul  — pull-based P2P forwarding (C1) fused with the MXU
                           consumer: burst-granularity pipelining (Fig. 6's
                           mechanism) applied to the TP all-gather.
  ring_reducescatter_matmul — the mirrored producer side: partial-sum
                           forwarding overlapped with matmul.
  multicast_stream       — the multicast NoC (C2): one source, chunked
                           store-and-forward to every ring member (wormhole
                           burst pipelining across the ICI).
  dma_double_buffer      — the IDMA/CDMA ISA pair (C5): tag-based async DMA
                           with double-buffered load/compute overlap.
  paged_attention        — the same IDMA/CDMA pipeline on the serving
                           path: decode attention DMAs each slot's live KV
                           blocks through the scalar-prefetched block table.
"""
