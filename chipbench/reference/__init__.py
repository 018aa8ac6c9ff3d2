"""Plain float32 references: jax.numpy, no kernels, nothing of the program."""
