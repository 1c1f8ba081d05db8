"""Plain references: the same mathematics written out in ``jax.numpy``,
importing nothing of the program, at ``highest`` matmul precision (or a
lower dtype, for the control)."""
