"""Parallelism layer: meshes, long-context sequence parallelism, and
model-parallel collectives built from the framework's own schedules.

The reference is a collectives library, not a trainer (SURVEY.md §2.7) —
its transferable long-context mechanism is segmentation + pipelining
(§5). This package is where that substrate becomes user-visible scale:
ring attention (blockwise attention with K/V rotating over the collective
axis, the eager-ring schedule applied to attention state) and Ulysses-
style all-to-all sequence parallelism, both composable inside shard_map
alongside the sequencer's collective schedule bodies.
"""

from .mesh import factorize_devices, make_mesh  # noqa: F401
from .pipeline import gpipe_schedule  # noqa: F401
from .ring_attention import ring_attention  # noqa: F401
from .ulysses import ulysses_attention  # noqa: F401
