"""Plain references: straight ``jax.numpy`` in float32 at ``highest`` matmul
precision, independent of ``photon_tpu`` (nothing is imported from it and
nothing it made is read)."""
