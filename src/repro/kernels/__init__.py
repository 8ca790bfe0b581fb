"""Pallas TPU kernels for the framework's compute hot spots.

deliver/ - fused incidence delivery: scalar-prefetch gather + mask +
           segment-combine over a dst-sorted CSR layout (the whole
           half-superstep data path; the ``delivery='pallas_fused'``
           design point), with the equivalent ELL+COO XLA lowering
           that every platform runs (Mosaic refuses the kernel's
           in-kernel row gather; see ``deliver/__init__.py``).
segsum/  - segment-sum as blocked one-hot matmul on the MXU (the MESH
           combine step: scatter-reduce -> dense systolic work);
           unsorted-fallback reference for the fused deliver kernel.
isect/   - hyperedge-pair bitset intersection (AND+popcount), with an
           in-kernel scalar-prefetch row gather.
flash/   - FlashAttention forward (prefill hot spot).

Each kernel ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with the interpret switch), ref.py (pure-jnp oracle).  Kernels are
an opt-in fast path; the jnp reference is the default execution path and
the oracle every sweep asserts against.
"""
