"""Multi-chip parallelism for the check engine.

The reference scales out as stateless replicas over a shared SQL database
(SURVEY §2 checklist: no collectives, no multi-process runtime exist there).
Here scale-out is a first-class device-mesh design:

* **query data-parallelism** (`shard_fast_check`, `shard_general_check`): the
  batch axis of checks is sharded over the mesh, the tuple graph is
  replicated — every device runs its query shard with zero cross-device
  traffic.  This is the throughput axis (BatchCheck, BASELINE config #4).
* **graph sharding** (`graphshard.sharded_check`): tuples partitioned by
  (namespace, object) hash across the mesh; each BFS level does local CSR
  gathers, routes cross-shard children with `lax.all_to_all` over ICI, and
  psum-merges the monotone found-bits — the capacity axis for graphs beyond
  one chip's HBM (BASELINE config #5).
"""

from ketotpu.parallel.graphshard import (
    build_sharded_snapshot,
    sharded_check,
    sharded_general_check,
)
from ketotpu.parallel.mesh import make_mesh, shard_fast_check, shard_general_check
from ketotpu.parallel.meshengine import MeshCheckEngine
from ketotpu.parallel.peerlink import HostLink, host_of

__all__ = [
    "HostLink",
    "MeshCheckEngine",
    "build_sharded_snapshot",
    "host_of",
    "make_mesh",
    "shard_general_check",
    "shard_fast_check",
    "sharded_check",
    "sharded_general_check",
]
