"""Custom TPU kernels (Pallas/Mosaic) for the framework's hot ops.

The reference's innermost loops run per-partition on Breeze/BLAS via JNI
(SURVEY.md §2.4); here the device compute path is XLA, with Pallas kernels
where data movement beyond XLA's reach pays — the slab-aligned sparse
gradient (:mod:`photon_tpu.ops.pallas_gather`), selected at run time by
:mod:`photon_tpu.ops.sparse_grad_select`."""
