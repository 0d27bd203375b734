"""Plain references: float32 `jax.numpy`, highest matmul precision, no
kernels, no cache. Independent of `paddle_tpu`."""
