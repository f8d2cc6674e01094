"""Plain references: each architecture's forward pass and loss in
``jax.numpy`` and float32 at ``jax.default_matmul_precision("highest")``,
with no kernels, no cache and no batching tricks. They read the system's
own parameter tree, so both sides see the same seeded weights."""
