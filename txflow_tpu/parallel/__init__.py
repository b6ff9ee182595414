"""Device-mesh parallelism for the vote-batch axis.

The reference has no data parallelism at all — votes are verified one at a
time under a mutex (reference types/vote_set.go:85-131). Here the "long
dimension" (concurrent in-flight tx x validator votes, SURVEY.md §5) is
sharded over a ``jax.sharding.Mesh``: each device verifies its shard of the
batch and partial stake tallies are combined with a single ``psum`` over
ICI — the workload's analog of sequence parallelism.
"""

from .mesh import (
    VOTE_AXIS,
    make_mesh,
    sharded_compact_step_packed_cached,
    sharded_verify_and_tally,
)

__all__ = [
    "make_mesh",
    "sharded_compact_step_packed_cached",
    "sharded_verify_and_tally",
    "VOTE_AXIS",
]
