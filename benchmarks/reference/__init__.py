"""Plain references: straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision. They import nothing of the program and
take nothing it has made; data comes from the seed through the
benchmark's own copies of the generators."""
