"""Plain references: the forward pass, loss and gradients of each
configuration in straightforward float32 `jax.numpy`, with no kernel, no
cache and no batching tricks.  They import nothing of the program and take
nothing the program made: weights come from `benchmark/weights.py`."""
