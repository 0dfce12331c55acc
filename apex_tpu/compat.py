"""The one jax this tree runs on (``pyproject.toml``: ``jax>=0.9``).

``shard_map`` is ``jax.shard_map``: a top-level API whose checked mode
tracks varying-manual-axes (vma) types, switched by ``check_vma``. The
package imports it from here so there is a single place that names the
supported surface.
"""

from jax import shard_map

__all__ = ["shard_map"]
