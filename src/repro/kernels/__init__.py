"""Kernels for the framework's compute hot spots.

deliver/ - fused incidence delivery (the ``delivery='pallas_fused'``
           design point): the whole half-superstep data path over a
           dst-sorted degree-class (sliced-ELL) layout, lowered through
           stock XLA ops on every platform; no Pallas kernel.
segsum/  - segment-sum as blocked one-hot matmul on the MXU (the MESH
           combine step: scatter-reduce -> dense systolic work).
isect/   - hyperedge-pair bitset intersection (AND+popcount), with an
           in-kernel scalar-prefetch row gather.
flash/   - FlashAttention forward (prefill hot spot).

Each Pallas kernel (segsum, isect, flash) ships <name>.py
(pl.pallas_call + BlockSpec), ops.py (jit'd wrapper with the interpret
switch), ref.py (pure-jnp oracle).  Kernels are an opt-in fast path;
the jnp reference is the default execution path and the oracle every
sweep asserts against.
"""
