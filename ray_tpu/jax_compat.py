"""Re-export of ``jax.shard_map`` under the repo's old shim name. Kept only
because the graftcheck fixtures (tests/_graftcheck_fixtures/) import it and
have findings pinned by line; code in ``ray_tpu/`` calls jax directly."""
from jax import shard_map  # noqa: F401
