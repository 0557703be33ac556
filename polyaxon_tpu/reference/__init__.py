"""Plain references: an architecture's forward pass in straightforward
``jax.numpy`` and float32, which the tests (and, through its own copy
under ``perfbench/reference/``, the benchmark) hold the system to."""
