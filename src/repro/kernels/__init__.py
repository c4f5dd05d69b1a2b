"""Device kernels for the compute hot-spots. Each subpackage: kernel.py
(the kernel), ops.py (jit'd wrapper, padding, custom_vjp), ref.py
(oracle).

The metadata path's kernels: ``phash`` (Pallas), and ``pkval``,
``hintchain`` and ``treeagg`` (XLA programs: their random lookups into
HBM-resident tables do not lower through Mosaic). :mod:`.mode` picks how
a Pallas kernel runs from the backend — compiled on the TPU, interpreted
on the CPU where the tests run — and places the compile cache.
"""
