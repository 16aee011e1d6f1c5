"""Plain references: each architecture's forward pass in straightforward
``jax.numpy``, no kernel, no cache, no batching tricks. A configuration
names the one it is held to (``"reference": "<module>"``)."""
