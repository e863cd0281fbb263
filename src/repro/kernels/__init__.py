"""Pallas TPU kernels for the paper's two compute hot-spots, the neighbour
aggregation (SpMM) and the colour-set combine (eMA):

* ``spmm_blocked/`` — "CSC-Split, TPU edition": blocked-ELL SpMM over
  (dst-block, src-block) tile pairs; MXU one-hot gather/scatter or VPU
  edge-loop inner modes.  kernel.py (pl.pallas_call + BlockSpec) / ops.py
  (jit wrapper + host preprocessing) / ref.py (pure-jnp oracle).
* ``spmm_ema/`` — the whole DP stage in one pass: the blocked-ELL
  aggregation feeds the eMA from a VMEM tile, so the aggregate product never
  reaches HBM (vertex axis on lanes, split tables in SMEM via scalar
  prefetch); the ``blocked`` backend's kernel.

Both validated against their oracles over shape/dtype sweeps in interpret
mode (tests/test_kernels.py).
"""
